"""Multi-device checks for the TUW JAX collectives.

Run in a SUBPROCESS (never under the main pytest process) so the 8 fake
host devices don't leak into other tests:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python child_collectives.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import build_gather_tree
from repro.core.distributions import NAMES, block_sizes
from repro.core.jax_collectives import (
    gatherv_shard, plan_gatherv, run_plan, set_dataplane,
    tree_metadata_exchange,
)
from repro.analysis import collective_bytes_from_hlo
from repro.tuner import PlannerService

PP = 8


def mesh1d():
    return jax.make_mesh((PP,), ("x",), axis_types=(AxisType.Auto,))


def rand_blocks(sizes, F, rng, dtype=np.float32):
    return [rng.standard_normal((s, F)).astype(dtype) for s in sizes]


def check_gatherv_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(0)
    for name in NAMES:
        for root in (0, 3, PP - 1):
            for scale in (3, 40):
                sizes = block_sizes(name, PP, scale, seed=5)
                blocks = rand_blocks(sizes, 4, rng)
                got, plan = run_plan("gatherv", plan_gatherv(sizes, root),
                                     mesh, "x", blocks)
                want = np.concatenate(blocks, axis=0)
                np.testing.assert_allclose(got, want, rtol=0, atol=0)
    print("gatherv oracle OK")


def check_gatherv_bucketing():
    mesh = mesh1d()
    rng = np.random.default_rng(1)
    sizes = block_sizes("spikes", PP, 50, seed=9)
    blocks = rand_blocks(sizes, 3, rng)
    got1, plan1 = run_plan("gatherv", plan_gatherv(sizes, 2, bucket_rounds=1),
                           mesh, "x", blocks)
    got3, plan3 = run_plan("gatherv", plan_gatherv(sizes, 2, bucket_rounds=3),
                           mesh, "x", blocks)
    np.testing.assert_allclose(got1, got3)
    assert plan3.tree_bytes_padded <= plan1.tree_bytes_padded, (
        plan1.tree_bytes_padded, plan3.tree_bytes_padded)
    assert plan1.tree_bytes_exact == plan3.tree_bytes_exact
    print(f"bucketing OK: padded {plan1.tree_bytes_padded} -> "
          f"{plan3.tree_bytes_padded} (exact {plan1.tree_bytes_exact})")


def check_scatterv_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(2)
    for name in NAMES:
        for root in (0, 5):
            sizes = block_sizes(name, PP, 17, seed=3)
            total = sum(sizes)
            data = rng.standard_normal((total, 2)).astype(np.float32)
            blocks, plan = run_plan("scatterv", plan_gatherv(sizes, root),
                                    mesh, "x", data, sizes)
            off = 0
            for i, s in enumerate(sizes):
                np.testing.assert_allclose(blocks[i], data[off: off + s])
                off += s
    print("scatterv oracle OK")


def check_int_dtype():
    mesh = mesh1d()
    rng = np.random.default_rng(7)
    sizes = block_sizes("random", PP, 9, seed=1)
    blocks = [rng.integers(0, 1000, (s, 5)).astype(np.int32) for s in sizes]
    got, _ = run_plan("gatherv", plan_gatherv(sizes, 4), mesh, "x", blocks)
    np.testing.assert_array_equal(got, np.concatenate(blocks, axis=0))
    print("int dtype OK")


def check_metadata_exchange():
    mesh = mesh1d()
    for seed in range(5):
        sizes = block_sizes("random", PP, 100, seed=seed)
        host_tree = build_gather_tree(sizes)  # free root

        @jax.jit
        def run(m):
            def body(ml):
                est, groot, total = tree_metadata_exchange(ml[0], "x", PP)
                return est[None], groot[None], total[None]
            return jax.shard_map(
                body, mesh=mesh, in_specs=P("x"), out_specs=P("x"))(m)

        m = jax.device_put(np.asarray(sizes, np.int32),
                           NamedSharding(mesh, P("x")))
        est, groot, total = run(m)
        assert int(groot[0]) == host_tree.root, (groot, host_tree.root)
        assert int(total[0]) == sum(sizes)
        assert int(est[0]) == sum(sizes) - sizes[host_tree.root]
        # all devices agree (fully distributed: everyone knows the root)
        assert len(set(np.asarray(groot).tolist())) == 1
    print("in-graph Lemma-3 metadata exchange OK")


def check_ragged_planner():
    mesh = mesh1d()
    rng = np.random.default_rng(3)
    svc = PlannerService(mesh, "x", quantum=16)
    for trial in range(6):
        sizes = [int(x) for x in rng.integers(1, 40, PP)]
        blocks = rand_blocks(sizes, 4, rng)
        got, _ = svc.gatherv(blocks, root=1)
        np.testing.assert_allclose(got, np.concatenate(blocks, axis=0))
    assert len(svc._compiled) <= 6  # bucketing caps distinct programs
    print(f"ragged planner OK (cache={len(svc._compiled)} programs for "
          "6 calls)")


def check_hlo_collectives():
    mesh = mesh1d()
    sizes = block_sizes("decreasing", PP, 64, seed=4)
    plan = plan_gatherv(sizes, 3)
    fn = jax.jit(jax.shard_map(
        lambda xl: gatherv_shard(xl, plan, "x"),
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    x = jnp.zeros((plan.p * plan.cap, 4), jnp.float32)
    compiled = fn.lower(jax.device_put(x, NamedSharding(mesh, P("x")))).compile()
    stats = collective_bytes_from_hlo(compiled.as_text())
    assert stats.ops.get("collective-permute", 0) >= len(plan.steps), stats.ops
    assert stats.total_bytes > 0
    print(f"HLO collectives OK: {dict(stats.ops)}, bytes={stats.total_bytes}")


if __name__ == "__main__":
    assert jax.device_count() == PP, jax.devices()
    set_dataplane("xla")  # CPU devices: the jnp slab reference
    check_gatherv_oracle()
    check_gatherv_bucketing()
    check_scatterv_oracle()
    check_int_dtype()
    check_metadata_exchange()
    check_ragged_planner()
    check_hlo_collectives()
    print("ALL MULTIDEVICE COLLECTIVE CHECKS PASSED")
