"""Multi-device checks for the PIPELINED (segmented) dataplane and the
Pallas slab backend.  Run in a SUBPROCESS (never under the main pytest
process) so the 8 fake host devices don't leak into other tests:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python child_pipeline.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
from jax.sharding import AxisType

from repro.core import jax_collectives as jc
from repro.core.distributions import block_sizes

PP = 8


def mesh1d():
    return jax.make_mesh((PP,), ("x",), axis_types=(AxisType.Auto,))


def check_pipelined_equals_monolithic():
    """The acceptance-criterion equivalence, on a real SPMD mesh: every op,
    S in {1, 2, 4}, byte-identical outputs."""
    mesh = mesh1d()
    rng = np.random.default_rng(0)
    sizes = block_sizes("spikes", PP, 25, seed=3)
    blocks = [rng.standard_normal((s, 3)).astype(np.float32) for s in sizes]
    want = np.concatenate(blocks, axis=0)
    S_mat = rng.integers(0, 10, (PP, PP))
    ab = [[rng.standard_normal((int(S_mat[i][j]), 2)).astype(np.float32)
           for j in range(PP)] for i in range(PP)]

    def run4(S):
        """gatherv, scatterv, allgatherv and alltoallv at ``S`` segments."""
        return (jc.run_plan("gatherv", jc.plan_gatherv(sizes, 2, segments=S),
                            mesh, "x", blocks),
                jc.run_plan("scatterv", jc.plan_gatherv(sizes, 2, segments=S),
                            mesh, "x", want, sizes)[0],
                jc.run_plan("allgatherv",
                            jc.plan_allgatherv(sizes, segments=S),
                            mesh, "x", blocks)[0],
                jc.run_plan("alltoallv",
                            jc.plan_alltoallv(S_mat, segments=S),
                            mesh, "x", ab)[0])

    (g1, _), s1, a1, t1 = run4(1)
    for S in (2, 4):
        (gS, plan), sS, aS, tS = run4(S)
        assert plan.segments == S and max(plan.stage_ids) < plan.num_stages
        np.testing.assert_array_equal(gS, g1)
        for a, b in zip(sS, s1):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(aS, a1)
        for a, b in zip(tS, t1):
            np.testing.assert_array_equal(a, b)
    print("pipelined == monolithic OK (4 ops, S in {2,4}, p=8)")

    # the MoE fast-path variants on a real mesh: payload-binned waves and
    # the direct pairwise schedule, pipelined per tree — all byte-identical
    from repro.core.composed import alltoallv_direct_schedule

    S_sizes = [[int(b.shape[0]) for b in row] for row in ab]
    tb, plan = jc.run_plan(
        "alltoallv", jc.plan_alltoallv(S_sizes, segments=2,
                                       wave_bin_ratio=2.0), mesh, "x", ab)
    assert plan.wave_bin_ratio == 2.0
    for a, b in zip(tb, t1):
        np.testing.assert_array_equal(a, b)
    td, plan = jc.run_plan(
        "alltoallv", jc.plan_alltoallv(
            S_sizes, segments=2, wave_bin_ratio=2.0,
            schedule=alltoallv_direct_schedule(S_sizes)), mesh, "x", ab)
    off_diag = sum(S_sizes[i][j] for i in range(PP) for j in range(PP)
                   if i != j)
    assert plan.tree_bytes_exact == off_diag  # direct: exact bytes
    for a, b in zip(td, t1):
        np.testing.assert_array_equal(a, b)
    print("moe fast path OK (binned waves + direct schedule, S=2, p=8)")


def check_pallas_slab_backend():
    """Run the Pallas slab kernels (interpret mode on CPU) through the
    full shard_map data plane of all six ops and compare against the jnp
    backend."""
    mesh = mesh1d()
    rng = np.random.default_rng(1)
    sizes = block_sizes("random", PP, 15, seed=5)
    blocks = [rng.standard_normal((s, 4)).astype(np.float32) for s in sizes]
    want = np.concatenate(blocks, axis=0)
    S_mat = rng.integers(0, 6, (PP, PP))
    ab = [[rng.standard_normal((int(S_mat[i][j]), 4)).astype(np.float32)
           for j in range(PP)] for i in range(PP)]
    contribs = [rng.standard_normal((sum(sizes), 4)).astype(np.float32)
                for _ in range(PP)]
    a2a = jc.plan_alltoallv(S_mat)
    rs_plan = jc.plan_reduce_scatterv(sizes)
    ar_plan = jc.plan_allreducev(sizes)
    jc.set_dataplane("xla")
    t_ref, _ = jc.run_plan("alltoallv", a2a, mesh, "x", ab)
    rs_ref, _ = jc.run_plan("reduce_scatterv", rs_plan, mesh, "x", contribs,
                            sizes)
    ar_ref, _ = jc.run_plan("allreducev", ar_plan, mesh, "x", contribs,
                            sizes)
    try:
        jc.set_dataplane("interpret")
        for S in (1, 3):
            gplan = jc.plan_gatherv(sizes, 0, segments=S)
            out, _ = jc.run_plan("gatherv", gplan, mesh, "x", blocks)
            np.testing.assert_array_equal(out, want)
            sc, _ = jc.run_plan("scatterv", gplan, mesh, "x", want, sizes)
            for a, b in zip(sc, blocks):
                np.testing.assert_array_equal(a, b)
            ag, _ = jc.run_plan("allgatherv",
                                jc.plan_allgatherv(sizes, segments=S),
                                mesh, "x", blocks)
            for j in range(PP):
                np.testing.assert_array_equal(ag[j], want)
        t, _ = jc.run_plan("alltoallv", a2a, mesh, "x", ab)
        for a, b in zip(t, t_ref):
            np.testing.assert_array_equal(a, b)
        rs, _ = jc.run_plan("reduce_scatterv", rs_plan, mesh, "x", contribs,
                            sizes)
        for a, b in zip(rs, rs_ref):
            np.testing.assert_array_equal(a, b)
        ar, _ = jc.run_plan("allreducev", ar_plan, mesh, "x", contribs,
                            sizes)
        np.testing.assert_array_equal(ar, ar_ref)
    finally:
        jc.set_dataplane("xla")
    print("pallas slab backend OK (six ops; gatherv/scatterv/allgatherv "
          "at S in {1,3})")


def check_pipelined_hlo_payloads_shrink():
    """The point of the slab dataplane: pipelined steps permute ~1/S-sized
    slabs, never the whole capacity buffer — visible in the lowered plan's
    max payload."""
    sizes = [4096] * PP
    mono = jc.plan_gatherv(sizes, 0)
    pipe = jc.plan_gatherv(sizes, 0, segments=4)
    mono_max = max(payload for _, payload, *_ in mono.steps)
    pipe_max = max(payload for _, payload, *_ in pipe.steps)
    assert pipe_max * 2 <= mono_max, (mono_max, pipe_max)
    assert pipe.tree_bytes_exact == mono.tree_bytes_exact
    print(f"slab payloads OK: max {mono_max} -> {pipe_max} rows at S=4")


if __name__ == "__main__":
    assert jax.device_count() == PP, jax.devices()
    jc.set_dataplane("xla")  # CPU devices: the jnp slab reference
    check_pipelined_equals_monolithic()
    check_pallas_slab_backend()
    check_pipelined_hlo_payloads_shrink()
    print("ALL MULTIDEVICE PIPELINE CHECKS PASSED")
