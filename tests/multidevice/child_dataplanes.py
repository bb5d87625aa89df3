"""Every executor on the ``"interpret"`` slab data plane (the Pallas
kernels on the kernels' row view) against the ``"xla"`` one (the jnp
oracles on the flat buffer), on 4 forced CPU devices, at a width whose
row view is (N, 2, 128) and at one whose row view is (N, 1, 96).
``alltoallv_skew`` is alltoallv on a skewed matrix with zero blocks and
a chip that sends nothing, where a padded slab reads past its sender's
input: alltoallv's capacity buffer and the output its ``slab_unpack``
kernel builds are not zeroed, and the interpreter leaves their unwritten
rows NaN, so a row that leaked into the output would show.  alltoallv's
rows past ``out_valid`` must read 0 on both data planes, or the verdict
is ``tail``.  Prints one ``DATAPLANE <op> <F>
<equal|differ|zero|nan|tail>`` line per executor and width.
Subprocess-only (XLA_FLAGS):

    PYTHONPATH=src python tests/multidevice/child_dataplanes.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, PartitionSpec as P  # noqa: E402

from repro.core import jax_collectives as jc  # noqa: E402

OPS = ("gatherv", "scatterv", "allgatherv", "alltoallv", "reduce_scatterv",
       "allreducev", "alltoallv_skew")
WIDTHS = (256, 96)
SIZES = [13, 0, 21, 6]                  # offsets 0, 13, 13, 34: unaligned
S = [[3, 0, 5, 2], [7, 1, 0, 4], [0, 6, 2, 1], [5, 2, 0, 0]]
# chip 0's last block (1 row, to chip 3) shares a step with a 22-row one
SKEW = [[4, 0, 30, 1], [17, 3, 0, 0], [0, 0, 0, 0], [1, 22, 0, 2]]


def reads_past_input(pl) -> bool:
    """Whether some step's padded slab runs past its sender's input."""
    return any(send[s] + payload > pl.in_starts[s] + sum(SKEW[s])
               for perm, payload, send, _, _ in pl.steps for s, _ in perm)


def plan(op: str):
    return {"gatherv": lambda: jc.plan_gatherv(SIZES, 1),
            "scatterv": lambda: jc.plan_gatherv(SIZES, 1),
            "allgatherv": lambda: jc.plan_allgatherv(SIZES),
            "alltoallv": lambda: jc.plan_alltoallv(S),
            "reduce_scatterv": lambda: jc.plan_reduce_scatterv(SIZES),
            "allreducev": lambda: jc.plan_allreducev(SIZES),
            "alltoallv_skew": lambda: jc.plan_alltoallv(SKEW)}[op]()


def tail_is_zero(op: str, pl, out: np.ndarray) -> bool:
    """Whether every chip's alltoallv output rows past ``out_valid`` are
    all zero bits (true of the other ops, which have no such rows)."""
    if not op.startswith("alltoallv"):
        return True
    per_chip = out.reshape(4, pl.out_rows, -1).view(np.uint32)
    return all(not per_chip[j, n:].any() for j, n in enumerate(pl.out_valid))


def run(mesh, op: str, pl, x, dataplane: str) -> np.ndarray:
    shard = getattr(jc, op.removesuffix("_skew") + "_shard")
    jc.set_dataplane(dataplane)
    fn = jax.jit(jax.shard_map(lambda xl: shard(xl, pl, "x"), mesh=mesh,
                               in_specs=P("x"), out_specs=P("x"),
                               check_vma=False))
    return np.asarray(fn(x).astype(jnp.float32))


def main():
    assert jax.device_count() == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
    assert reads_past_input(plan("alltoallv_skew"))
    for F in WIDTHS:
        for k, op in enumerate(OPS):
            pl = plan(op)
            rows = (pl.buf_rows if op == "scatterv" else
                    pl.in_rows if op in ("reduce_scatterv", "allreducev")
                    else pl.cap)
            x = jax.random.normal(jax.random.key(10 * F + k), (4 * rows, F),
                                  jnp.bfloat16)
            want = run(mesh, op, pl, x, "xla")
            got = run(mesh, op, pl, x, "interpret")
            same = want.shape == got.shape and np.array_equal(
                want.view(np.uint32), got.view(np.uint32))
            verdict = ("nan" if np.isnan(got).any() else
                       "zero" if not want.any() else
                       "tail" if not (tail_is_zero(op, pl, want)
                                      and tail_is_zero(op, pl, got)) else
                       "equal" if same else "differ")
            print(f"DATAPLANE {op} {F} {verdict}", flush=True)


if __name__ == "__main__":
    main()
