"""Smoke run of the ragged-collective data plane on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one 2x2 v5e host, all four chips

Default phase (one chip):

* every slab op of the executor (``slab_extract``, ``slab_merge``,
  ``slab_step``, ``slab_merge_add``, ``slab_step_reduce``), compiled, at
  the row widths of mixtral-8x7b (d_model 4096) and deepseek-moe-16b
  (2048) in bf16, over the step tables of a 4-rank mixtral dispatch
  plan, each result compared bitwise with the jnp reference
  (``repro.kernels.ragged_gather.ref``);
* the six ragged collectives once each through ``PlannerService`` on a
  one-device mesh at mixtral width.

``--chips 4`` phase (nothing else): the six collectives through
``PlannerService`` on a 4-device mesh for a mixtral and a deepseek
dispatch, each result compared with the NumPy oracle (byte-identical;
the reductions in the plan's fixed fold order) and with XLA's own
collective on the same data (``all_to_all`` at capacity,
``all_gather``, ``psum_scatter``, ``psum``).

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``; it is printed only when every check passed.  Without
a TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOKENS_PER_CHIP = 4096
MODELS = ("mixtral-8x7b", "deepseek-moe-16b")


def log(msg: str) -> None:
    print(msg, flush=True)


def dispatch_matrix(model: str, p: int, tokens: int, seed: int = 0):
    """S[i][j]: routed rows chip ``i`` sends to chip ``j`` when every chip
    holds ``tokens`` tokens, each routed to the model's top-k of its
    experts under the zipf-skewed loads of ``moe_load_fractions``, and
    chip ``j`` holds experts ``j*E/p .. (j+1)*E/p - 1``."""
    import numpy as np

    from benchmarks.common import moe_load_fractions
    from repro.configs import get_config

    moe = get_config(model).moe
    chip_load = moe_load_fractions(moe.n_experts, "zipf",
                                   seed).reshape(p, -1).sum(axis=1)
    rng = np.random.default_rng(seed)
    return np.stack([rng.multinomial(tokens * moe.top_k, chip_load)
                     for _ in range(p)])


def _bits(x):
    import jax.numpy as jnp
    from jax import lax

    return lax.bitcast_convert_type(x, jnp.uint16 if x.dtype.itemsize == 2
                                    else jnp.uint32)


def check_slab_ops(plan, widths, dtype, *, interpret: bool = False,
                   seed: int = 0) -> None:
    """Run every slab op over ``plan``'s step tables at each width and
    compare it bitwise with its jnp reference; raise on any mismatch."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.ragged_gather import ops, ref

    def same(a, b):
        return bool(jnp.all(_bits(a.reshape(b.shape)) == _bits(b)))

    steps = plan.steps
    for F in widths:
        k0, k1 = jax.random.split(jax.random.PRNGKey(seed + F))
        buf = jax.random.normal(k0, (plan.buf_rows, F), jnp.float32
                                ).astype(dtype)
        kernels, refs = {}, {}
        for name in ("slab_extract", "slab_merge", "slab_step",
                     "slab_merge_add", "slab_step_reduce"):
            kernel = functools.partial(getattr(ops, name), interpret=interpret)
            reference = getattr(ref, name + "_ref")
            static = (2,) if name == "slab_extract" else (
                (5,) if "step" in name else ())
            kernels[name] = jax.jit(
                lambda *a, _k=kernel: _k(*(ops.row_view(x) if jnp.ndim(x) == 2
                                           else x for x in a)),
                static_argnums=static)
            refs[name] = jax.jit(reference, static_argnums=static)
        checked = 0
        for k, (perm, payload, send, recv, valid) in enumerate(steps):
            slab = jax.random.normal(jax.random.fold_in(k1, k),
                                     (payload, F), jnp.float32).astype(dtype)
            nxt = steps[k + 1] if k + 1 < len(steps) else steps[0]
            for src, dst in perm:
                cases = {
                    "slab_extract": (buf, int(send[src]), payload),
                    "slab_merge": (buf, slab, int(recv[dst]),
                                   int(valid[dst])),
                    "slab_merge_add": (buf, slab, int(recv[dst]),
                                       int(valid[dst])),
                    "slab_step": (buf, slab, int(recv[dst]), int(valid[dst]),
                                  int(nxt[2][dst]), nxt[1]),
                    "slab_step_reduce": (buf, slab, int(recv[dst]),
                                         int(valid[dst]), int(nxt[2][dst]),
                                         nxt[1]),
                }
                for name, args in cases.items():
                    if not interpret and checked < len(cases):
                        hlo = kernels[name].lower(*args).compile().as_text()
                        if "tpu_custom_call" not in hlo:
                            raise AssertionError(
                                f"{name} F={F}: no Pallas kernel in the "
                                "compiled program")
                    got = kernels[name](*args)
                    want = refs[name](*args)
                    got = got if isinstance(got, tuple) else (got,)
                    want = want if isinstance(want, tuple) else (want,)
                    if not all(same(g, w) for g, w in zip(got, want)):
                        raise AssertionError(
                            f"{name} F={F} step {k} rank {dst}: kernel "
                            "differs from the jnp reference")
                    checked += 1
        log(f"slab ops F={F} {jnp.dtype(dtype).name}: {checked} calls over "
            f"{len(steps)} plan steps (buf_rows={plan.buf_rows}), "
            f"{'interpreted' if interpret else 'compiled Pallas'}, all "
            "bitwise equal to the jnp reference")


def _random_rows(rng, rows: int, F: int, dtype):
    import numpy as np

    return rng.standard_normal((rows, F), np.float32).astype(dtype)


def run_six_ops(svc, S, F: int, dtype, *, label: str, xla_mesh=None,
                seed: int = 0) -> None:
    """Run the six collectives through ``svc`` for dispatch matrix ``S``
    and compare with the NumPy oracle (and, given ``xla_mesh``, with
    XLA's collectives); raise on any mismatch."""
    import numpy as np

    from repro.core.pipeline import (execute_allreducev_plan_numpy,
                                     execute_reduce_scatterv_plan_numpy)

    rng = np.random.default_rng(seed)
    p = len(S)
    sizes = [int(v) for v in S.sum(axis=0)]   # rows each chip's experts got
    blocks = [_random_rows(rng, n, F, dtype) for n in sizes]
    flat = np.concatenate(blocks)
    a2a = [[_random_rows(rng, int(S[i][j]), F, dtype) for j in range(p)]
           for i in range(p)]
    contribs = [_random_rows(rng, sum(sizes), F, dtype) for _ in range(p)]

    def timed(name, fn):
        fn()                                   # compile + first run
        t0 = time.perf_counter()
        out = fn()
        log(f"  {label} {name}: warm wall {time.perf_counter() - t0:.4f} s "
            "(one smoke run, host staging included; not a metric)")
        return out

    def equal(a, b, what):
        if a.shape != b.shape or a.tobytes() != b.tobytes():
            raise AssertionError(f"{label} {what}: differs from the oracle")

    got, _ = timed("gatherv", lambda: svc.gatherv(blocks, root=0))
    equal(got, flat, "gatherv")
    got, _ = timed("scatterv", lambda: svc.scatterv(flat, sizes, root=0))
    for j in range(p):
        equal(got[j], blocks[j], f"scatterv rank {j}")
    ag, _ = timed("allgatherv", lambda: svc.allgatherv(blocks))
    for j in range(p):
        equal(ag[j], flat, f"allgatherv rank {j}")
    recv, _ = timed("alltoallv", lambda: svc.alltoallv(a2a))
    for j in range(p):
        equal(recv[j], np.concatenate([a2a[i][j] for i in range(p)]),
              f"alltoallv rank {j}")
    rs, plan = timed("reduce_scatterv",
                     lambda: svc.reduce_scatterv(contribs, sizes))
    for j, want in enumerate(execute_reduce_scatterv_plan_numpy(plan,
                                                                contribs)):
        equal(rs[j], want, f"reduce_scatterv rank {j}")
    ar, plan = timed("allreducev", lambda: svc.allreducev(contribs, sizes))
    for j, want in enumerate(execute_allreducev_plan_numpy(plan, contribs)):
        equal(ar[j], want, f"allreducev rank {j}")
    log(f"  {label}: six ops equal to the NumPy oracle "
        "(reductions in the plan's fold order)")
    if xla_mesh is not None:
        compare_with_xla(xla_mesh, S, blocks, a2a, contribs, ag, recv, rs,
                         ar, label=label)


def compare_with_xla(mesh, S, blocks, a2a, contribs, ag, recv, rs, ar, *,
                     label: str) -> None:
    """XLA's own collectives on the same data, padded to capacity:
    ``all_to_all`` and ``all_gather`` must agree byte for byte,
    ``psum_scatter`` and ``psum`` (another fold order) within bf16
    rounding."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    p = len(S)
    F = blocks[0].shape[1]
    dtype = blocks[0].dtype
    sizes = [b.shape[0] for b in blocks]
    offs = np.concatenate([[0], np.cumsum(sizes)])
    spec = NamedSharding(mesh, P("x"))

    def xla(body, x):
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                   out_specs=P("x")))
        return np.asarray(fn(jax.device_put(x, spec)))

    def padded(parts, cap):
        out = np.zeros((len(parts), cap, F), dtype)
        for i, part in enumerate(parts):
            out[i, : part.shape[0]] = part
        return out

    cap = int(S.max())
    x = np.stack([padded(row, cap) for row in a2a])     # (p, p, cap, F)
    out = xla(lambda v: jax.lax.all_to_all(v[0], "x", 0, 0)[None],
              x).reshape(p, p, cap, F)
    for j in range(p):
        want = np.concatenate([out[j, i, : S[i][j]] for i in range(p)])
        if recv[j].tobytes() != want.tobytes():
            raise AssertionError(f"{label} alltoallv rank {j} != all_to_all")
    cap = max(sizes)
    out = xla(lambda v: jax.lax.all_gather(v, "x", tiled=True)[None],
              padded(blocks, cap)).reshape(p, p, cap, F)
    for j in range(p):
        want = np.concatenate([out[j, i, : sizes[i]] for i in range(p)])
        if ag[j].tobytes() != want.tobytes():
            raise AssertionError(f"{label} allgatherv rank {j} != all_gather")
    # |sum_i c_i| over every partial sum is at most sum_i |c_i|
    scale = sum(np.abs(c.astype(np.float32)) for c in contribs)
    segs = np.stack([padded([c[offs[j]: offs[j + 1]] for j in range(p)], cap)
                     for c in contribs])                # (p, p, cap, F)
    out = xla(lambda v: jax.lax.psum_scatter(v[0], "x", scatter_dimension=0,
                                             tiled=True)[None], segs)
    out = out.reshape(p, cap, F)
    worst = 0.0
    for j in range(p):
        worst = max(worst, _close(rs[j], out[j, : sizes[j]],
                                  scale[offs[j]: offs[j + 1]], p,
                                  f"{label} reduce_scatterv rank {j} vs "
                                  "psum_scatter"))
    out = xla(lambda v: jax.lax.psum(v, "x"),
              np.concatenate(contribs)).reshape(p, -1, F)
    for j in range(p):
        worst = max(worst, _close(ar[j], out[j], scale, p,
                                  f"{label} allreducev rank {j} vs psum"))
    log(f"  {label}: alltoallv == all_to_all and allgatherv == all_gather "
        "byte for byte; reductions within bf16 rounding of psum_scatter / "
        f"psum (max |diff| {worst:.4g})")


def _close(a, b, scale, p: int, what: str) -> float:
    """Max |a - b|; raise unless within ``p`` bf16 ulps of ``scale`` (the
    sum of the terms' magnitudes, which bounds every partial sum): two
    fold orders of ``p`` terms, each rounding at most ``p - 1`` times."""
    import numpy as np

    a = a.astype(np.float32)
    b = b.astype(np.float32)
    diff = np.abs(a - b)
    if a.shape != b.shape or not np.all(diff <= p * 2.0 ** -7 * scale):
        raise AssertionError(f"{what}: beyond bf16 rounding")
    return float(diff.max(initial=0.0))


def _widths(widths):
    from repro.configs import get_config

    return widths or {m: get_config(m).d_model for m in MODELS}


def one_chip_phase(dtype, *, tokens: int = TOKENS_PER_CHIP,
                   widths: dict | None = None,
                   interpret: bool = False) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.core import jax_collectives as jc
    from repro.tuner import PlannerService

    widths = _widths(widths)
    S = dispatch_matrix("mixtral-8x7b", 4, tokens)
    plan = jc.plan_alltoallv(S)
    log(f"phase 1: slab ops over the {len(plan.steps)}-step plan of a 4-rank "
        f"mixtral dispatch (row sums {S.sum(axis=1).tolist()}), "
        f"F in {tuple(widths.values())}")
    check_slab_ops(plan, tuple(widths.values()), dtype, interpret=interpret)

    mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=1)
    F = widths["mixtral-8x7b"]
    log(f"phase 2: six ops through PlannerService on a one-device mesh, "
        f"F={F}, data plane {jc.dataplane()!r}")
    run_six_ops(svc, np.asarray([[int(S[0].sum())]]), F, dtype,
                label="mixtral p=1")


def four_chip_phase(dtype, *, tokens: int = TOKENS_PER_CHIP,
                    widths: dict | None = None) -> None:
    import jax
    from jax.sharding import AxisType

    from repro.core import jax_collectives as jc
    from repro.tuner import PlannerService

    widths = _widths(widths)
    mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=1)
    log(f"four-chip phase: data plane {jc.dataplane()!r}, {tokens} "
        "tokens per chip")
    for seed, model in enumerate(MODELS):
        S = dispatch_matrix(model, 4, tokens, seed)
        log(f"{model}: F={widths[model]}, routed rows per chip "
            f"{S.sum(axis=1).tolist()}, rows per expert chip "
            f"{S.sum(axis=0).tolist()}")
        run_six_ops(svc, S, widths[model], dtype, label=model, xla_mesh=mesh,
                    seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: slab kernels + six ops on one chip; "
                    "4: the six ops on a 4-chip mesh, nothing else")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r} devices", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.device_kind} x {len(jax.devices())}, "
        f"jax {jax.__version__}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(jnp.bfloat16)
    else:
        one_chip_phase(jnp.bfloat16)
    log(f"all checks passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
