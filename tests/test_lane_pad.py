"""Rows whose width the TPU does not lay out for a DMA at any row run
lane-padded: the capacity buffer holds them at ``ops.lane_width``, the
planner prices them at the bytes the ppermutes move, and the executors
cut the padding off where rows leave the buffer.  The executor cases run
in one child process on 4 forced CPU devices with the kernels
interpreted (``tests/multidevice/child_lane_pad.py``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import jax_collectives as jc
from repro.kernels.ragged_gather import ops
from repro.obs.metrics import REGISTRY
from repro.tuner import PlannerService

CHILD = os.path.join(os.path.dirname(__file__), "multidevice",
                     "child_lane_pad.py")
OPS = ("gatherv", "scatterv", "allgatherv", "alltoallv", "reduce_scatterv",
       "allreducev")


@pytest.mark.parametrize("F, dtype, width", [
    (2688, jnp.bfloat16, 3072),   # Nemotron-3-Nano-30B-A3B: 21 lane groups
    (2560, jnp.bfloat16, 3072),
    (2304, jnp.bfloat16, 3072),
    (3584, jnp.bfloat16, 4096),
    (4608, jnp.bfloat16, 5120),
    (7680, jnp.bfloat16, 8192),
    (7168, jnp.bfloat16, 7168),   # whole tiles: as they are
    (4096, jnp.bfloat16, 4096),
    (2048, jnp.bfloat16, 2048),
    (256, jnp.bfloat16, 256),     # 2 groups: the bf16 tile of a small row
    (128, jnp.bfloat16, 256),
    (384, jnp.bfloat16, 512),
    (384, jnp.int8, 512),
    (2688, jnp.float32, 2688),    # 32-bit rows need whole groups only
    (96, jnp.bfloat16, 96),       # not whole lane groups: left as it is
])
def test_lane_width(F, dtype, width):
    assert ops.lane_width(F, dtype) == width


def test_plan_record_prices_the_moved_row_bytes():
    """A plan for 2688-wide bf16 rows is priced and keyed at the 6144 B
    the ppermutes move on the kernels' data plane, at the 5376 B given
    on the ``"xla"`` one; the module registry's gauge holds the last."""
    S = [[0, 40, 7, 3], [9, 0, 12, 30], [5, 8, 0, 2], [1, 1, 1, 0]]
    svc = PlannerService(mesh=None, quantum=1)
    prev = jc.dataplane()
    try:
        recs = {}
        for plane in ("interpret", "xla"):
            jc.set_dataplane(plane)
            recs[plane] = svc.plan_record("alltoallv", S, dtype="bfloat16",
                                          row_bytes=5376)
            gauge = REGISTRY.snapshot()["gauges"]["moved_row_bytes"]
            assert gauge == recs[plane].moved_row_bytes
    finally:
        jc.set_dataplane(prev)
    assert (recs["interpret"].row_bytes, recs["interpret"].moved_row_bytes) \
        == (5376, 6144)
    assert (recs["xla"].row_bytes, recs["xla"].moved_row_bytes) \
        == (5376, 5376)
    assert recs["interpret"].serial != recs["xla"].serial
    # a width that needs no padding keeps its key and its price
    assert jc.moved_row_bytes(8192, np.dtype(jnp.bfloat16)) == 8192
    assert jc.moved_row_bytes(1, "float32") == 1


@pytest.fixture(scope="module")
def child(child_env):
    env = dict(child_env, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    lines = [line.split() for line in res.stdout.splitlines()]
    return ({(w[1], int(w[2])): w[3] for w in lines if w[0] == "LANE_PAD"},
            {int(w[1]): (int(w[2]), int(w[3])) for w in lines
             if w[0] == "MOVED"},
            {w[1]: w[2] for w in lines if w[0] == "QUANT"})


@pytest.mark.parametrize("F", [384, 2688])
@pytest.mark.parametrize("op", OPS)
def test_executor_equals_oracle_at_lane_padded_width(op, F, child):
    assert child[0].get((op, F)) == "equal", child[0]


@pytest.mark.parametrize("op", OPS)
def test_executor_at_quantized_strides(op, child):
    """Through a service of quantum 4 the plan is built at sizes rounded
    up to multiples of 4: the true blocks are staged at the plan's longer
    strides and read back from them, bitwise equal to the oracle."""
    assert child[2].get(op) == "equal", child[2]


@pytest.mark.parametrize("F, moved", [(384, 1024), (2688, 6144)])
def test_plan_record_moves_padded_rows(F, moved, child):
    assert child[1][F] == (2 * F, moved)
