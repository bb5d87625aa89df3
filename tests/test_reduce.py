"""Reduction collectives: reduce_scatterv / allreducev.

Differential suite against the NumPy sum oracle across process counts,
pipeline depths, and load shapes; bitwise-repeatability (deterministic,
rank-ordered fold order); the fused-add kernel vs its jnp reference;
the degenerate-input hardening pass (satellite: zero-size contributions
never produce empty ppermute steps, NaN padding overheads, or slab
crashes — on the byte-moving planners AND the reduce planners that
inherit their guards); dtype-keyed plan caching; and the hierarchical
refit-drop surfacing.  Multi-device execution runs in a subprocess
child (tests/multidevice/child_reduce.py) on 8 fake host devices.
"""
import math
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core.composed import (
    alltoallv_schedule, reduce_scatterv_direct_schedule,
    reduce_scatterv_halving_schedule, reduce_scatterv_schedule,
    simulate_reduce_dataflow,
)
from repro.core.costmodel import (CostParams, HierarchicalCostParams,
                                  HostTopology)
from repro.core.jax_collectives import (
    plan_allgatherv, plan_allreducev, plan_alltoallv, plan_gatherv,
    plan_reduce_scatterv,
)
from repro.core.pipeline import (
    execute_allreducev_plan_numpy, execute_reduce_scatterv_plan_numpy,
)
from repro.tuner import (Calibration, OnlineCalibrator, PlannerService,
                         SyntheticTimingBackend, enumerate_candidates)

CHILD = os.path.join(os.path.dirname(__file__), "multidevice",
                     "child_reduce.py")

SHAPES = ("uniform", "zipf", "single_hot", "all_zero")


def _sizes(shape: str, p: int, seed: int = 0, scale: int = 9) -> list[int]:
    rng = np.random.default_rng(seed)
    if shape == "uniform":
        return [scale] * p
    if shape == "zipf":
        w = np.maximum(1, 4 * scale / np.arange(1, p + 1) ** 1.2)
        return [int(x) for x in rng.permutation(w.astype(np.int64))]
    if shape == "single_hot":
        m = [1] * p
        m[min(3, p - 1)] = scale * p
        return m
    if shape == "all_zero":
        return [0] * p
    raise ValueError(shape)


def _offs(m):
    return np.concatenate([[0], np.cumsum(m)]).astype(np.int64)


# --------------------------------------------------------------------------
# schedule layer
# --------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 3, 8, 64])
@pytest.mark.parametrize("shape", SHAPES)
def test_reduce_schedules_validate_and_cover(p, shape):
    """Every schedule family passes the reduction dataflow simulator:
    each owner's segment folds in every rank EXACTLY once."""
    m = _sizes(shape, p, seed=p)
    for build in (reduce_scatterv_schedule, reduce_scatterv_direct_schedule):
        simulate_reduce_dataflow(build(m))
    if not (p & (p - 1)):
        simulate_reduce_dataflow(reduce_scatterv_halving_schedule(m))


def test_halving_requires_power_of_two():
    for p in (3, 6, 12):
        with pytest.raises(ValueError):
            reduce_scatterv_halving_schedule([2] * p)


def test_dataflow_simulator_rejects_reduce_schedules():
    """The overwrite-semantics simulator must refuse reduction schedules
    instead of silently mis-modelling the fused adds."""
    with pytest.raises(ValueError):
        reduce_scatterv_schedule([3, 1, 4, 1]).simulate_dataflow()


def test_direct_schedule_bytes_exact():
    m = [5, 0, 7, 3]
    sched = reduce_scatterv_direct_schedule(m)
    moved = sum(t.size for rnd in sched.rounds for t in rnd)
    # every rank sends every other rank's segment once: (p-1) * sum(m)
    assert moved == (len(m) - 1) * sum(m)


# --------------------------------------------------------------------------
# differential suite vs the NumPy sum oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 3, 8, 64])
@pytest.mark.parametrize("segments", [1, 2, 4])
@pytest.mark.parametrize("shape", SHAPES)
def test_reduce_scatterv_differential(p, segments, shape):
    m = _sizes(shape, p, seed=p + segments)
    total, offs = int(sum(m)), _offs(m)
    rng = np.random.default_rng(1_000 * p + segments)
    contribs = [rng.standard_normal((total, 2)) for _ in range(p)]  # f64
    plan = plan_reduce_scatterv(m, segments=segments)
    got = execute_reduce_scatterv_plan_numpy(plan, contribs)
    want = (np.sum(contribs, axis=0) if p else np.zeros((0, 2)))
    for j in range(p):
        assert got[j].shape[0] == m[j]
        np.testing.assert_allclose(got[j], want[offs[j]: offs[j + 1]],
                                   rtol=0, atol=1e-9)


@pytest.mark.parametrize("p", [2, 3, 8])
@pytest.mark.parametrize("shape", SHAPES)
def test_allreducev_differential(p, shape):
    m = _sizes(shape, p, seed=p)
    rng = np.random.default_rng(p)
    contribs = [rng.standard_normal((int(sum(m)), 3)) for _ in range(p)]
    plan = plan_allreducev(m, segments=2)
    out = execute_allreducev_plan_numpy(plan, contribs)
    want = np.sum(contribs, axis=0)
    for j in range(p):  # every device: the full reduced vector
        np.testing.assert_allclose(out[j], want, rtol=0, atol=1e-9)


@pytest.mark.parametrize("build", [None, reduce_scatterv_direct_schedule,
                                   reduce_scatterv_halving_schedule])
def test_all_schedule_families_reduce_exactly(build):
    p = 8
    m = _sizes("zipf", p, seed=3)
    total, offs = int(sum(m)), _offs(m)
    rng = np.random.default_rng(7)
    contribs = [rng.standard_normal((total, 2)) for _ in range(p)]
    sched = None if build is None else build(m)
    plan = plan_reduce_scatterv(m, schedule=sched)
    got = execute_reduce_scatterv_plan_numpy(plan, contribs)
    want = np.sum(contribs, axis=0)
    for j in range(p):
        np.testing.assert_allclose(got[j], want[offs[j]: offs[j + 1]],
                                   rtol=0, atol=1e-9)


def test_bitwise_repeatable_and_pipelining_invariant():
    """float32 fold order is a pure function of the size signature: two
    runs agree BITWISE, and the pipelined plan agrees bitwise with the
    monolithic one (same per-row fold sequence, re-timed only)."""
    p = 8
    m = _sizes("zipf", p, seed=5)
    rng = np.random.default_rng(9)
    contribs = [rng.standard_normal((int(sum(m)), 4)).astype(np.float32)
                for _ in range(p)]
    mono = plan_reduce_scatterv(m)
    a = execute_reduce_scatterv_plan_numpy(mono, contribs)
    b = execute_reduce_scatterv_plan_numpy(mono, contribs)
    piped = plan_reduce_scatterv(m, segments=4)
    c = execute_reduce_scatterv_plan_numpy(piped, contribs)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(x, z)


# --------------------------------------------------------------------------
# fused-add slab kernel vs jnp reference (interpret mode)
# --------------------------------------------------------------------------

def test_slab_merge_add_kernel_matches_ref_bitwise():
    import jax.numpy as jnp

    from repro.kernels.ragged_gather.ops import slab_merge_add
    from repro.kernels.ragged_gather.ref import slab_merge_add_ref

    rng = np.random.default_rng(0)
    buf = jnp.asarray(rng.standard_normal((12, 4)).astype(np.float32))
    slab = jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32))
    for start, valid in ((0, 5), (3, 2), (7, 0)):
        ref = slab_merge_add_ref(buf, slab, start, valid)
        ker = slab_merge_add(buf, slab, start, valid, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_slab_step_reduce_kernel_matches_ref_bitwise():
    import jax.numpy as jnp

    from repro.kernels.ragged_gather.ops import slab_step_reduce
    from repro.kernels.ragged_gather.ref import slab_step_reduce_ref

    rng = np.random.default_rng(1)
    buf = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    got = jnp.asarray(rng.standard_normal((6, 4)).astype(np.float32))
    for recv_start, recv_valid, send_start in ((0, 6, 8), (4, 3, 0),
                                               (9, 0, 2)):
        r_buf, r_slab = slab_step_reduce_ref(buf, got, recv_start,
                                             recv_valid, send_start, 6)
        k_buf, k_slab = slab_step_reduce(buf, got, recv_start, recv_valid,
                                         send_start, 6, interpret=True)
        np.testing.assert_array_equal(np.asarray(r_buf), np.asarray(k_buf))
        np.testing.assert_array_equal(np.asarray(r_slab),
                                      np.asarray(k_slab))


def test_fused_add_mask_preserves_negative_zero():
    """Masked rows must keep the accumulator bitwise untouched: the
    fused add selects ``cur`` outright (``cur + 0`` would flip -0.0)."""
    import jax.numpy as jnp

    from repro.kernels.ragged_gather.ops import slab_merge_add
    from repro.kernels.ragged_gather.ref import slab_merge_add_ref

    buf = jnp.full((6, 3), -0.0, jnp.float32)
    slab = jnp.ones((6, 3), jnp.float32)
    for fn in (slab_merge_add_ref,
               lambda *a: slab_merge_add(*a, interpret=True)):
        out = np.asarray(fn(buf, slab, 0, 0))  # valid=0: all rows masked
        assert np.signbit(out).all(), "masked add rewrote -0.0 as +0.0"


# --------------------------------------------------------------------------
# degenerate-input hardening (satellite): zero sizes, p=1, all-zero
# --------------------------------------------------------------------------

def _all_plans_zero(p):
    yield plan_gatherv([0] * p, 0)
    yield plan_allgatherv([0] * p)
    yield plan_alltoallv(np.zeros((p, p), np.int64))
    yield plan_reduce_scatterv([0] * p)
    yield plan_allreducev([0] * p)


@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_all_zero_problems_lower_cleanly(p):
    """m_i == 0 everywhere: plans must validate with finite (0.0) padding
    overhead and no empty ppermute steps."""
    for plan in _all_plans_zero(p):
        assert plan.tree_bytes_exact == 0
        assert math.isfinite(plan.padding_overhead)
        assert plan.padding_overhead == 0.0
        for step in plan.steps:
            assert len(step[0]) > 0, "empty ppermute perm emitted"


def test_p1_single_rank_plans():
    """p=1 collectives are pure local copies: zero steps, zero comm."""
    plans = (plan_gatherv([5], 0), plan_allgatherv([5]),
             plan_alltoallv(np.array([[5]], np.int64)),
             plan_reduce_scatterv([5]), plan_allreducev([5]))
    for plan in plans:
        assert len(plan.steps) == 0
        assert plan.tree_bytes_exact == 0
        assert math.isfinite(plan.padding_overhead)


def test_zero_segment_senders_harden_everywhere():
    """Interleaved zero contributors (silent ranks, empty experts) across
    bucketing, pipelining, and wave-binning — no crash, no NaN, and the
    reduce result is still exact."""
    m = [0, 7, 0, 0, 3, 0, 12, 0]
    S = np.zeros((8, 8), np.int64)
    S[1, :] = 3
    S[:, 6] = 5
    S[4, 4] = 11          # diagonal self-block
    for kw in ({"bucket_rounds": 2}, {"segments": 2},
               {"wave_bin_ratio": 2.0}):
        assert math.isfinite(plan_alltoallv(S, **kw).padding_overhead)
        assert math.isfinite(plan_allgatherv(m, **kw).padding_overhead)
        assert math.isfinite(
            plan_reduce_scatterv(m, **kw).padding_overhead)
    # the legalizer never leaves a rank sending to itself or an empty wave
    sched = alltoallv_schedule(S)
    for rnd in sched.rounds:
        assert rnd, "empty round emitted"
        for t in rnd:
            assert t.size > 0
    rng = np.random.default_rng(2)
    contribs = [rng.standard_normal((int(sum(m)), 2)) for _ in range(8)]
    offs = _offs(m)
    plan = plan_reduce_scatterv(m, segments=2)
    got = execute_reduce_scatterv_plan_numpy(plan, contribs)
    want = np.sum(contribs, axis=0)
    for j in range(8):
        np.testing.assert_allclose(got[j], want[offs[j]: offs[j + 1]],
                                   rtol=0, atol=1e-9)


# --------------------------------------------------------------------------
# tuner plumbing: candidates, dtype-keyed cache, hierarchical refit drop
# --------------------------------------------------------------------------

FLAT = CostParams(1e-6, 2e-11, "s", "byte")


def test_reduce_candidate_families_enumerated():
    m = [3, 9, 1, 6, 2, 8, 4, 5]
    for op in ("reduce_scatterv", "allreducev"):
        names = [c.name for c in enumerate_candidates(
            op, m, None, FLAT, view="dataplane", buckets=(1, 2),
            segments=(1, 2), wave_bins=(2.0,))]
        assert any(n.startswith("tuw_reduce") for n in names)
        assert "halving_reduce" in names          # p=8 is a power of two
        assert "direct_reduce" in names
        assert any("S=2" in n for n in names)     # pipelined variants
        assert any("g2" in n for n in names)      # wave-binned variants
    # non-power-of-two p: the halving family must drop out
    names7 = [c.name for c in enumerate_candidates(
        "reduce_scatterv", m[:7], None, FLAT, view="dataplane")]
    assert not any(n.startswith("halving") for n in names7)
    assert any(n.startswith("tuw_reduce") for n in names7)


def test_service_selects_and_caches_reduce_plans():
    svc = PlannerService(mesh=None, quantum=1, params=FLAT)
    m = [4, 13, 2, 8, 1, 6, 9, 3]
    r1 = svc.plan_record("reduce_scatterv", m, row_bytes=128)
    r2 = svc.plan_record("reduce_scatterv", m, row_bytes=128)
    assert r1.serial == r2.serial          # cache hit, not a re-plan
    assert r1.plan.sizes == tuple(m)
    ar = svc.plan_record("allreducev", m, row_bytes=128)
    assert ar.plan.rs.sizes == tuple(m)
    # allreducev chains an allgatherv over the SAME segment layout
    assert list(ar.plan.rs.offsets) == list(ar.plan.ag.in_starts)


def test_dtype_keys_separate_reduce_plans():
    """Satellite: float32 / bfloat16 / int32 reductions of the same size
    vector must occupy DISTINCT cache entries — accumulation dtype
    changes the compiled executable even when byte schedules match."""
    svc = PlannerService(mesh=None, quantum=1, params=FLAT)
    m = [5, 2, 9, 4, 1, 7, 3, 6]
    recs = {dt: svc.plan_record("reduce_scatterv", m, dtype=dt,
                                row_bytes=rb)
            for dt, rb in (("float32", 16), ("bfloat16", 8),
                           ("int32", 16))}
    serials = {r.serial for r in recs.values()}
    assert len(serials) == 3, "dtype collision in the plan cache"
    # and the compiled-executable key includes the dtype string too
    again = svc.plan_record("reduce_scatterv", m, dtype="float32",
                            row_bytes=16)
    assert again.serial == recs["float32"].serial


def test_hierarchical_refit_observations_kept_per_axis():
    """Satellite (telemetry plane): hierarchical race observations used
    to be measured and then DROPPED from refitting (warn-once in PR 6);
    a per-link-class HierarchicalOnlineCalibrator now keeps every one of
    them, nothing is dropped, and no warning fires."""
    topo = HostTopology(2, 4)
    hp = HierarchicalCostParams(
        CostParams(1e-6, 2e-11, "s", "byte"),
        CostParams(50e-6, 16e-11, "s", "byte"), topo)
    machine = SyntheticTimingBackend(alpha_s=2e-6,
                                     beta_s_per_byte=2.5e-11, noise=0.0)
    svc = PlannerService(mesh=None, quantum=1, params=hp,
                         measure=machine.measure, top_k=2)
    from repro.tuner import HierarchicalOnlineCalibrator
    assert isinstance(svc.calibrator, HierarchicalOnlineCalibrator)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        svc.plan_record("reduce_scatterv", [3, 5, 2, 7, 1, 4, 6, 2])
        svc.plan_record("allgatherv", [2, 2, 9, 1, 5, 3, 8, 4])
    assert not [w for w in caught
                if issubclass(w.category, RuntimeWarning)]
    assert svc.calibrator.n_observations >= 4  # 2 ops, top_k=2: all kept
    # the race-driven refit sharpened the hierarchical fit in place —
    # still per-link-class params over the SAME topology
    assert isinstance(svc.params, HierarchicalCostParams)
    assert svc.params.topology == topo
    # sharpening alone never bumps the params epoch (that's drift's job)
    assert svc.stats["params_epoch"] == 0


def test_online_calibrator_rejected_in_hierarchical_mode():
    """A flat 2-weight calibrator still cannot serve hierarchical params
    — the service demands the 4-weight one."""
    topo = HostTopology(2, 4)
    hp = HierarchicalCostParams(
        CostParams(1e-6, 2e-11, "s", "byte"),
        CostParams(50e-6, 16e-11, "s", "byte"), topo)
    guess = Calibration(1e-6, 1e-11, r2=1.0, n_samples=1, backend="guess")
    with pytest.raises(ValueError, match="HierarchicalOnlineCalibrator"):
        PlannerService(mesh=None, params=hp,
                       calibrator=OnlineCalibrator(guess))


def test_flat_service_ledger_not_polluted_by_reduce_measurements():
    """Flat online loop still refits cleanly when reduce ops race."""
    guess = Calibration(1e-3, 1e-12, r2=1.0, n_samples=1, backend="guess")
    true = SyntheticTimingBackend(alpha_s=1e-6, beta_s_per_byte=1e-7,
                                  noise=0.0)
    svc = PlannerService(mesh=None, quantum=1, calibration=guess,
                         measure=true.measure, top_k=3,
                         calibrator=OnlineCalibrator(guess,
                                                     prior_weight=0.1))
    svc.plan_record("reduce_scatterv", [1, 1, 1, 1, 1, 1, 1, 50_000])
    assert not isinstance(svc.params, HierarchicalCostParams)


# --------------------------------------------------------------------------
# multi-device lane (subprocess: 8 fake host devices)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_multidevice_reduce(child_env):
    res = subprocess.run([sys.executable, CHILD], env=child_env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ALL REDUCE MULTIDEVICE CHECKS PASSED" in res.stdout
