"""JAX collective layer tests.

Single-device invariants run inline; everything needing >1 device runs the
child script in a subprocess with its own XLA_FLAGS (see conftest notes).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import build_gather_tree
from repro.core.jax_collectives import plan_gatherv
from repro.core.distributions import NAMES, block_sizes
from repro.kernels.ragged_gather.kernel import KERNEL_NAMES

CHILD = os.path.join(os.path.dirname(__file__), "multidevice",
                     "child_collectives.py")


# ------------------------------------------------------------ plan invariants

@given(st.lists(st.integers(min_value=0, max_value=500), min_size=2,
                max_size=64),
       st.integers(min_value=0, max_value=63),
       st.integers(min_value=1, max_value=4))
@settings(max_examples=80, deadline=None)
def test_plan_tables_consistent(sizes, root_idx, buckets):
    root = root_idx % len(sizes)
    plan = plan_gatherv(sizes, root, bucket_rounds=buckets)
    assert plan.total == sum(sizes)
    assert plan.tree_bytes_exact <= plan.tree_bytes_padded
    # exact bytes equal the tree's moved bytes (paper's linear cost)
    tree = build_gather_tree(list(sizes), root=root)
    assert plan.tree_bytes_exact == tree.total_bytes_moved()
    seen_pairs = set()
    for perm, payload, send_start, recv_start, recv_valid in plan.steps:
        assert payload >= 1
        for (s, d) in perm:
            assert (s, d) not in seen_pairs  # each edge sent exactly once
            seen_pairs.add((s, d))
            assert 0 <= send_start[s] <= plan.total
            assert recv_valid[d] <= payload
        # ppermute legality: unique sources, unique destinations per step
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
    assert len(seen_pairs) == sum(1 for e in tree.edges if e.size > 0)


@pytest.mark.parametrize("name", NAMES)
def test_bucketing_never_increases_padded_bytes(name):
    sizes = block_sizes(name, 64, 1000, seed=2)
    p1 = plan_gatherv(sizes, 11, bucket_rounds=1)
    p4 = plan_gatherv(sizes, 11, bucket_rounds=4)
    assert p4.tree_bytes_padded <= p1.tree_bytes_padded
    assert p4.tree_bytes_exact == p1.tree_bytes_exact


def test_padding_overhead_reported():
    sizes = block_sizes("spikes", 64, 1000, seed=2)
    plan = plan_gatherv(sizes, 11)
    assert plan.padding_overhead >= 0.0


# ------------------------------------------------------- multi-device child

@pytest.mark.slow
def test_multidevice_collectives(child_env):
    res = subprocess.run(
        [sys.executable, CHILD], env=child_env, capture_output=True,
        text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "ALL MULTIDEVICE COLLECTIVE CHECKS PASSED" in res.stdout


# ------------------------------------------------- executor phase scopes

SCOPE_SIZES = [5, 0, 9, 3]
SCOPE_S = [[2, 0, 1, 3], [4, 1, 0, 2], [0, 3, 3, 1], [1, 2, 0, 0]]


EXECUTORS = ["gatherv", "scatterv", "allgatherv", "alltoallv",
             "reduce_scatterv", "allreducev"]


def _lowered_scopes(op: str, dataplane: str, F: int, dtype) -> tuple:
    """``(scopes, hlo)``: the executor of ``op`` lowered for a 4-rank mesh
    on ``dataplane`` at (rows, F) ``dtype`` input, and every name in its
    ops' ``op_name`` metadata.  The process has one CPU device, so the
    program is lowered against an abstract mesh, which needs no
    devices."""
    import re

    import jax
    from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

    from repro.core import jax_collectives as jc

    plan = {"gatherv": lambda: jc.plan_gatherv(SCOPE_SIZES, 0),
            "scatterv": lambda: jc.plan_gatherv(SCOPE_SIZES, 0),
            "allgatherv": lambda: jc.plan_allgatherv(SCOPE_SIZES),
            "alltoallv": lambda: jc.plan_alltoallv(SCOPE_S),
            "reduce_scatterv": lambda: jc.plan_reduce_scatterv(SCOPE_SIZES),
            "allreducev": lambda: jc.plan_allreducev(SCOPE_SIZES)}[op]()
    rows = (plan.buf_rows if op == "scatterv" else
            plan.in_rows if op in ("reduce_scatterv", "allreducev") else
            plan.cap)
    mesh = AbstractMesh((4,), ("x",))
    shard = getattr(jc, op + "_shard")
    prev = jc.dataplane()
    jc.set_dataplane(dataplane)
    try:
        fn = jax.jit(jax.shard_map(lambda xl: shard(xl, plan, "x"),
                                   mesh=mesh, in_specs=P("x"),
                                   out_specs=P("x"), check_vma=False))
        x = jax.ShapeDtypeStruct((4 * rows, F), dtype,
                                 sharding=NamedSharding(mesh, P("x")))
        lowered = fn.trace(x).lower(lowering_platforms=("cpu",))
    finally:
        jc.set_dataplane(prev)
    hlo = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    scopes = {scope for name in re.findall(r'op_name="([^"]*)"', hlo)
              for scope in name.split("/")}
    return scopes, hlo


@pytest.mark.parametrize("op", EXECUTORS)
def test_executor_phases_carry_scopes(op):
    """Each executor, lowered for a 4-rank mesh on the ``"xla"`` data
    plane, names its phases in the ``op_name`` metadata: the fill of the
    capacity buffer, every ppermute and every step's slab op, and the
    output taken from the buffer where the executor takes one."""
    import re

    import jax.numpy as jnp

    from repro.core import jax_collectives as jc

    scopes, hlo = _lowered_scopes(op, "xla", 8, jnp.float32)
    want = {jc.PPERMUTE, jc.STEP}
    if op != "scatterv":                  # scatterv runs on its input
        want.add(jc.FILL)
    if op in ("scatterv", "alltoallv", "reduce_scatterv"):
        want.add(jc.UNPACK)
    assert want <= scopes, scopes
    assert scopes & set(jc.SCOPES) == want, scopes
    # the slab ops carry the name of the kernel they stand for
    steps = re.findall(r'op_name="ragged\.step/([^/"]*)/', hlo)
    assert set(steps) <= set(KERNEL_NAMES) and steps, steps


@pytest.mark.parametrize("op", EXECUTORS)
def test_lane_padded_rows_carry_lane_pad_scope(op):
    """On the kernels' data plane, rows of 384 bf16 lanes (3 lane groups)
    are padded to 512 where they enter the capacity buffer and cut back
    where they leave it, both under ``ragged.lane_pad``; 256 lanes need
    neither."""
    import jax.numpy as jnp

    from repro.core import jax_collectives as jc

    scopes, _ = _lowered_scopes(op, "interpret", 384, jnp.bfloat16)
    assert jc.LANE_PAD in scopes and jc.STEP in scopes, scopes
    scopes, _ = _lowered_scopes(op, "interpret", 256, jnp.bfloat16)
    assert jc.LANE_PAD not in scopes and jc.STEP in scopes, scopes
