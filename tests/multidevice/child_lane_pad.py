"""The six collectives through ``PlannerService`` on 4 forced CPU devices,
the slab kernels interpreted, at row widths the capacity buffer holds
lane-padded (``ops.lane_width``): 384 bf16 runs at 512 and 2688, the
hidden size of Nemotron-3-Nano-30B-A3B, at 3072.  Each result is
compared bit for bit with the NumPy oracle (the reductions in the plan's
fold order).  Prints one ``LANE_PAD <op> <F> <equal|differ>`` line per
collective and width, and one ``MOVED <F> <row bytes> <moved row
bytes>`` line per width from the plan records.  Then the six again at
384 through a service of quantum 4, whose plans are built at sizes
rounded up to multiples of 4, so every block is staged at a stride
longer than itself: one ``QUANT <op> <equal|differ>`` line per
collective.  Subprocess-only (XLA_FLAGS):

    PYTHONPATH=src python tests/multidevice/child_lane_pad.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import jax_collectives as jc  # noqa: E402
from repro.core.pipeline import (  # noqa: E402
    execute_allreducev_plan_numpy, execute_reduce_scatterv_plan_numpy)
from repro.tuner import PlannerService  # noqa: E402

WIDTHS = (384, 2688)
S = np.array([[3, 0, 5, 2], [7, 1, 0, 4], [0, 6, 2, 1], [5, 2, 0, 0]])


def rows(rng, n: int, F: int):
    return rng.standard_normal((n, F), np.float32).astype(jnp.bfloat16)


def same(a, b) -> bool:
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def at_plan_offsets(c, sizes, plan):
    """The flat contribution ``c`` (segments of ``sizes`` rows, one after
    another) laid out as ``plan`` reads it: segment ``j`` at
    ``plan.offsets[j]``, zero rows elsewhere."""
    out = np.zeros((plan.total, c.shape[1]), c.dtype)
    for j, (o, n) in enumerate(zip(np.cumsum([0, *sizes[:-1]]), sizes)):
        out[plan.offsets[j]: plan.offsets[j] + n] = c[o: o + n]
    return out


def verdicts(svc, F: int) -> dict:
    """``{op: equal}`` for the six collectives at width ``F``."""
    rng = np.random.default_rng(F)
    p = len(S)
    sizes = [int(v) for v in S.sum(axis=0)]
    blocks = [rows(rng, n, F) for n in sizes]
    flat = np.concatenate(blocks)
    a2a = [[rows(rng, int(S[i][j]), F) for j in range(p)] for i in range(p)]
    contribs = [rows(rng, sum(sizes), F) for _ in range(p)]
    out = {}
    got, _ = svc.gatherv(blocks, root=1)
    out["gatherv"] = same(got, flat)
    got, _ = svc.scatterv(flat, sizes, root=1)
    out["scatterv"] = all(same(g, b) for g, b in zip(got, blocks))
    got, _ = svc.allgatherv(blocks)
    out["allgatherv"] = all(same(got[j], flat) for j in range(p))
    got, _ = svc.alltoallv(a2a)
    out["alltoallv"] = all(
        same(got[j], np.concatenate([a2a[i][j] for i in range(p)]))
        for j in range(p))
    got, plan = svc.reduce_scatterv(contribs, sizes)
    want = execute_reduce_scatterv_plan_numpy(
        plan, [at_plan_offsets(c, sizes, plan) for c in contribs])
    out["reduce_scatterv"] = all(
        same(g, w[:n]) for g, w, n in zip(got, want, sizes))
    got, plan = svc.allreducev(contribs, sizes)
    want = execute_allreducev_plan_numpy(
        plan, [at_plan_offsets(c, sizes, plan) for c in contribs])
    out["allreducev"] = all(
        same(got[j], np.concatenate([w[o: o + n] for o, n in
                                     zip(plan.offsets, sizes)]))
        for j, w in enumerate(want))
    return out


def main():
    assert jax.device_count() == 4, jax.devices()
    jc.set_dataplane("interpret")
    mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
    for F in WIDTHS:
        svc = PlannerService(mesh=mesh, axis_name="x", quantum=1)
        for op, ok in verdicts(svc, F).items():
            print(f"LANE_PAD {op} {F} {'equal' if ok else 'differ'}",
                  flush=True)
        rec = svc.plan_record("alltoallv", S, dtype="bfloat16",
                              row_bytes=2 * F)
        print(f"MOVED {F} {rec.row_bytes} {rec.moved_row_bytes}", flush=True)
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=4)
    for op, ok in verdicts(svc, WIDTHS[0]).items():
        print(f"QUANT {op} {'equal' if ok else 'differ'}", flush=True)


if __name__ == "__main__":
    main()
