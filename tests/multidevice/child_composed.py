"""Multi-device checks for the COMPOSED TUW collectives (allgatherv /
alltoallv).  Run in a SUBPROCESS (never under the main pytest process) so
the 8 fake host devices don't leak into other tests:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python child_composed.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
from jax.sharding import AxisType

from repro.core.composed import independent_scatter_bytes
from repro.core.distributions import NAMES, block_sizes
from repro.core.jax_collectives import (
    plan_allgatherv, plan_alltoallv, run_plan, set_dataplane,
)

PP = 8


def mesh1d():
    return jax.make_mesh((PP,), ("x",), axis_types=(AxisType.Auto,))


def check_allgatherv_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(0)
    for name in NAMES:
        sizes = block_sizes(name, PP, 13, seed=4)
        blocks = [rng.standard_normal((s, 4)).astype(np.float32)
                  for s in sizes]
        outs, plan = run_plan("allgatherv", plan_allgatherv(sizes), mesh,
                              "x", blocks)
        want = np.concatenate(blocks, axis=0)
        for j in range(PP):  # EVERY device holds the rank-ordered buffer
            np.testing.assert_allclose(outs[j], want, rtol=0, atol=0)
    print("allgatherv oracle OK (all devices, rank order)")


def check_alltoallv_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(1)
    for seed in range(3):
        S = rng.integers(0, 12, (PP, PP))
        S[seed] = 0  # a silent sender too
        blocks = [[rng.standard_normal((int(S[i][j]), 3)).astype(np.float32)
                   for j in range(PP)] for i in range(PP)]
        res, plan = run_plan("alltoallv", plan_alltoallv(S), mesh, "x",
                             blocks)
        for j in range(PP):
            # rank order of the received buffer: sources ascending
            want = np.concatenate(
                [blocks[i][j] for i in range(PP)], axis=0).reshape(-1, 3)
            np.testing.assert_allclose(res[j], want, rtol=0, atol=0)
        # bytes-moved: exactly p independent rooted scatter trees
        assert plan.tree_bytes_exact == independent_scatter_bytes(S), (
            plan.tree_bytes_exact, independent_scatter_bytes(S))
    print("alltoallv oracle OK (rank order + exact bytes)")


def check_alltoallv_bucketing():
    mesh = mesh1d()
    rng = np.random.default_rng(2)
    S = rng.integers(0, 40, (PP, PP))
    blocks = [[rng.standard_normal((int(S[i][j]), 2)).astype(np.float32)
               for j in range(PP)] for i in range(PP)]
    res1, p1 = run_plan("alltoallv", plan_alltoallv(S, bucket_rounds=1),
                        mesh, "x", blocks)
    res3, p3 = run_plan("alltoallv", plan_alltoallv(S, bucket_rounds=3),
                        mesh, "x", blocks)
    for a, b in zip(res1, res3):
        np.testing.assert_allclose(a, b, rtol=0, atol=0)
    assert p3.tree_bytes_exact == p1.tree_bytes_exact
    assert p3.tree_bytes_padded <= p1.tree_bytes_padded, (
        p1.tree_bytes_padded, p3.tree_bytes_padded)
    assert len(p3.steps) >= len(p1.steps)
    print(f"alltoallv bucketing OK: padded {p1.tree_bytes_padded} -> "
          f"{p3.tree_bytes_padded} over {len(p1.steps)} -> {len(p3.steps)} "
          "ppermutes")


def check_allgatherv_bucketing():
    mesh = mesh1d()
    rng = np.random.default_rng(3)
    sizes = block_sizes("spikes", PP, 60, seed=8)
    blocks = [rng.standard_normal((s, 2)).astype(np.float32) for s in sizes]
    o1, p1 = run_plan("allgatherv", plan_allgatherv(sizes, bucket_rounds=1),
                      mesh, "x", blocks)
    o2, p2 = run_plan("allgatherv", plan_allgatherv(sizes, bucket_rounds=3),
                      mesh, "x", blocks)
    np.testing.assert_allclose(o1, o2, rtol=0, atol=0)
    assert p2.tree_bytes_padded <= p1.tree_bytes_padded
    print("allgatherv bucketing OK")


def check_int_dtype_alltoallv():
    mesh = mesh1d()
    rng = np.random.default_rng(4)
    S = rng.integers(0, 7, (PP, PP))
    blocks = [[rng.integers(0, 1000, (int(S[i][j]), 5)).astype(np.int32)
               for j in range(PP)] for i in range(PP)]
    res, _ = run_plan("alltoallv", plan_alltoallv(S), mesh, "x", blocks)
    for j in range(PP):
        want = np.concatenate(
            [blocks[i][j] for i in range(PP)], axis=0).reshape(-1, 5)
        np.testing.assert_array_equal(res[j], want)
    print("alltoallv int dtype OK")


def check_plan_vs_hlo_step_count():
    """Each plan step lowers to at least one collective-permute."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    from repro.analysis import collective_bytes_from_hlo
    from repro.core.jax_collectives import alltoallv_shard

    mesh = mesh1d()
    rng = np.random.default_rng(5)
    S = rng.integers(1, 9, (PP, PP))
    plan = plan_alltoallv(S)
    fn = jax.jit(jax.shard_map(
        lambda xl: alltoallv_shard(xl, plan, "x"),
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    x = jnp.zeros((PP * plan.cap, 4), jnp.float32)
    compiled = fn.lower(
        jax.device_put(x, NamedSharding(mesh, P("x")))).compile()
    stats = collective_bytes_from_hlo(compiled.as_text())
    assert stats.ops.get("collective-permute", 0) >= len(plan.steps), stats.ops
    print(f"HLO OK: {dict(stats.ops)} for {len(plan.steps)} plan steps")


if __name__ == "__main__":
    assert jax.device_count() == PP, jax.devices()
    set_dataplane("xla")  # CPU devices: the jnp slab reference
    check_allgatherv_oracle()
    check_alltoallv_oracle()
    check_alltoallv_bucketing()
    check_allgatherv_bucketing()
    check_int_dtype_alltoallv()
    check_plan_vs_hlo_step_count()
    print("ALL COMPOSED MULTIDEVICE CHECKS PASSED")
