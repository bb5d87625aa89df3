"""PlannerService tests that need no devices (mesh=None plan path):
warm-cache behavior, persistence across service instances, selection
plumbing, and the bounded plan cache."""
import pickle

import numpy as np
import pytest

from repro.core.costmodel import CostParams
from repro.core.distributions import block_sizes
from repro.tuner import (Calibration, OnlineCalibrator, PlannerService,
                         SyntheticTimingBackend)


def test_warm_plan_is_cache_hit_with_stable_identity():
    """Acceptance: a repeated MoE-dispatch size signature replans in O(1) —
    hit counter increments, plan identity stable, no reconstruction."""
    svc = PlannerService(mesh=None, quantum=128)
    rng = np.random.default_rng(0)
    S = rng.integers(0, 4096, (16, 16)).tolist()
    r1 = svc.plan_record("alltoallv", S)
    assert (svc.plan_hits, svc.plan_misses) == (0, 1)
    r2 = svc.plan_record("alltoallv", S)
    assert (svc.plan_hits, svc.plan_misses) == (1, 1)
    assert r2 is r1 and r2.plan is r1.plan
    # ragged jitter inside the same quantization bucket also hits
    Sq = np.asarray(svc._key("alltoallv", S, None, "f", 1).signature)
    jitter = np.where(Sq > 0, np.maximum(Sq - 63, 1), 0).tolist()
    assert svc.plan_record("alltoallv", jitter) is r1
    assert svc.plan_hits == 2


def test_plan_persists_across_service_instances(tmp_path):
    cache_dir = str(tmp_path / "plans")
    sizes = block_sizes("decreasing", 16, 1000, seed=2)
    svc1 = PlannerService(mesh=None, quantum=64, cache_dir=cache_dir)
    r1 = svc1.plan_record("gatherv", sizes, root=3)
    svc2 = PlannerService(mesh=None, quantum=64, cache_dir=cache_dir)
    r2 = svc2.plan_record("gatherv", sizes, root=3)
    assert (svc2.plan_hits, svc2.plan_misses) == (1, 0)
    assert r2.algo == r1.algo
    assert pickle.dumps(r2.plan, protocol=4) == pickle.dumps(r1.plan,
                                                             protocol=4)


def test_distinct_ops_roots_and_dtypes_get_distinct_plans():
    svc = PlannerService(mesh=None, quantum=64)
    sizes = block_sizes("random", 8, 500, seed=1)
    svc.plan_record("gatherv", sizes, root=0)
    svc.plan_record("gatherv", sizes, root=1)
    svc.plan_record("scatterv", sizes, root=0)
    svc.plan_record("gatherv", sizes, root=0, dtype="bfloat16")
    svc.plan_record("allgatherv", sizes)
    assert svc.plan_misses == 5 and svc.plan_hits == 0
    assert len(svc.cache) == 5


def test_selected_plans_execute_nothing_without_mesh():
    svc = PlannerService(mesh=None)
    blocks = [np.zeros((4, 2), np.float32)] * 4
    with pytest.raises(RuntimeError, match="plan-only"):
        svc.gatherv(blocks, root=0)
    with pytest.raises(ValueError, match="unknown op"):
        svc.plan_record("bcast", [1, 2])
    with pytest.raises(ValueError, match="needs a root"):
        svc.plan_record("gatherv", [1, 2])


def test_row_bytes_scaling_can_flip_bucket_choice():
    """Selection happens in bytes: with wide rows (β-dominated) extra
    bucket rounds pay for themselves by not padding small transfers to a
    skewed round's maximum; with narrow rows the α term dominates and
    bucket-1 must win."""
    # fixed root 0: the huge block crosses in round 0 next to 1-row sends
    sizes = [1, 100_000, 1, 1, 1, 1, 1, 1]
    lat = PlannerService(mesh=None, quantum=1,
                         params=CostParams(1e-3, 1e-12, "s", "byte"))
    rec_lat = lat.plan_record("gatherv", sizes, root=0, row_bytes=1)
    # the DP optimal tree ties the TUW tree exactly here (same shape in
    # the α-dominated regime), so either name may take the argmin — the
    # claim under test is the bucket, not the family
    assert rec_lat.algo in ("tuw(b=1)", "opt(b=1)"), rec_lat.costs
    costs_lat = dict(rec_lat.costs)
    assert costs_lat["tuw(b=1)"] <= min(
        v for k, v in costs_lat.items() if k.startswith("tuw("))
    bw = PlannerService(mesh=None, quantum=1,
                        params=CostParams(1e-9, 1e-7, "s", "byte"))
    rec_bw = bw.plan_record("gatherv", sizes, root=0, row_bytes=65_536)
    # bandwidth-dominated: padding the seven 1-row sends to 100k rows is
    # what costs; the winner avoids it (direct sends or more buckets) and
    # within the TUW family extra bucket rounds now beat bucket-1
    assert rec_bw.algo != "tuw(b=1)", rec_bw.costs
    costs = dict(rec_bw.costs)
    assert costs["tuw(b=4)"] < costs["tuw(b=1)"]


def test_online_measurement_loop_updates_service_params():
    guess = Calibration(1e-3, 1e-12, r2=1.0, n_samples=1, backend="guess")
    true = SyntheticTimingBackend(alpha_s=1e-6, beta_s_per_byte=1e-7,
                                  noise=0.0)
    svc = PlannerService(mesh=None, quantum=1, calibration=guess,
                         measure=true.measure, top_k=3,
                         calibrator=OnlineCalibrator(guess, prior_weight=0.1))
    before = svc.params
    svc.plan_record("allgatherv", [1, 1, 1, 1, 1, 1, 1, 100_000])
    after = svc.params
    assert after is not before
    # the refit moved beta decisively toward the true machine
    assert abs(np.log10(after.beta / 1e-7)) < abs(np.log10(before.beta / 1e-7))


def test_plan_cache_is_bounded():
    """The plan cache keeps at most ``max_cached_plans`` records and
    counts what it evicts."""
    svc = PlannerService(mesh=None, max_cached_plans=2, quantum=1)
    for i in range(4):
        svc.plan_record("gatherv", [i + 1, 2, 3, 4], root=0)
    assert len(svc.cache) == 2 and svc.cache.evictions == 2
