"""One rank's slab data plane for an MoE exchange, on one chip.

A pass is what ``repro.core.jax_collectives._apply_steps`` runs around
its ppermutes for the dispatch and the combine of one routing draw, at
the rank that receives the most rows in that draw: the entry
``row_view``, the leading ``slab_extract``, the fused ``slab_step`` of
every step and the trailing ``slab_merge``, at the plan's real offsets
and payloads, then the exit reshape.  The ppermute is left out, so each
step's input slab is the slab the rank itself just extracted; nothing
stands in for the absent chips or their traffic.  Each direction is one
jitted program that updates its buffer in place (donated), as the
executor's buffer is updated in place.

Program entries driven: ``repro.tuner.PlannerService.plan_record`` (at
set-up) and the slab ops of the selected data plane
(``repro.core.jax_collectives._slab_ops``).
"""
from __future__ import annotations

import numpy as np

from chipbench import routing
from chipbench.drivers import annotation

E2E = {"dataplane_ms": "ms"}


class Driver:
    def __init__(self, config: dict, traffic: dict, devices, seed: int, *,
                 trace: bool):
        import jax
        import jax.numpy as jnp

        if len(devices) != 1:
            raise ValueError("the slab data plane runs on one chip")
        self.device = devices[0]
        self.F = int(config["hidden_size"])
        self.dtype = jnp.dtype(config["dtype"])
        self.row_bytes = self.F * self.dtype.itemsize
        self.pool = routing.routing_pool(config, traffic)
        self.order = routing.cycle_order(len(self.pool), seed)
        self.key = routing.payload_key(seed)
        self.ranks = [int(np.argmax(S.sum(axis=0))) for S in self.pool]
        self.plans, self.progs = compile_programs(
            self.pool, self.ranks, self.dtype, self.F, self.device)
        self.shapes = shapes = [(plan.buf_rows, self.F)
                                for plans in self.plans for plan in plans]

        def make(key):
            return tuple(routing.payload(jax.random.fold_in(key, b), shape,
                                         self.dtype)
                         for b, shape in enumerate(shapes))

        with jax.default_device(self.device):
            self.bufs = list(jax.jit(make)(self.key))
        self.passes = [0] * len(shapes)
        self.ran: list[int] = []
        for d in range(len(self.pool)):   # first run of every program
            self.step(d, draw=d)
        self.ran.clear()

    def step(self, i: int, *, draw: int | None = None,
             annotate: bool = False) -> None:
        """One pass (dispatch then combine) of draw ``order[i]``, ending in
        ``block_until_ready``."""
        import jax.numpy as jnp

        d = int(self.order[i % len(self.order)]) if draw is None else draw
        r = jnp.int32(self.ranks[d])
        span = annotation(annotate)
        with span("launch"):
            for k in (2 * d, 2 * d + 1):
                self.bufs[k] = self.progs[d][k - 2 * d](self.bufs[k], r)
                self.passes[k] += 1
        with span("wait"):
            self.bufs[2 * d + 1].block_until_ready()
            self.bufs[2 * d].block_until_ready()
        self.ran.append(d)

    @staticmethod
    def end_to_end(window_s: float, times: list[float]) -> dict:
        return {"dataplane_ms": 1e3 * window_s / len(times)}

    def segments(self, units: int) -> dict:
        n = len(self.pool)
        units = max(2 * n, -(-units // n) * n)
        draws = [int(self.order[i % n]) for i in range(units)]

        def lib():
            for i in range(units):
                self.step(i, annotate=True)

        return {"lib": (lib, draws)}

    def layer_context(self) -> dict:
        return {"row_bytes": self.row_bytes, "pool": self.pool,
                "plans": self.plans, "ranks": self.ranks,
                "ran": list(self.ran)}

    def free(self) -> None:
        self.progs.clear()

    def check(self, precision: str | None = None) -> dict:
        """Mismatched elements of every buffer the timed programs left,
        against the plain slab semantics applied to a buffer regenerated
        from the seed as often as the programs ran on it.

        ``precision="float8_e4m3fn"`` computes the reference through that
        type: the control, which has to come out wrong."""
        import jax
        import jax.numpy as jnp

        dtype, key = self.dtype, self.key
        bad, wrong = 0, 0
        for b, shape in enumerate(self.shapes):
            d, direction = divmod(b, 2)
            plan = self.plans[d][direction]
            src = _compose(slab_rows(plan.steps, self.ranks[d],
                                     plan.buf_rows), self.passes[b])

            def count(key, got, src, b=b, shape=shape):
                init = routing.payload(jax.random.fold_in(key, b), shape,
                                       dtype)
                if precision:
                    init = routing.lowered(init, precision)
                return jnp.sum(routing.as_bits(init[src])
                               != routing.as_bits(got))

            with jax.default_device(self.device):
                n = int(jax.jit(count)(key, self.bufs[b], jnp.asarray(src)))
            self.bufs[b] = None
            bad += n
            wrong += int(n > 0)
        return {"checks": {"mismatched_elements": (bad, 0)},
                "answers": len(self.shapes), "wrong": wrong}


def compile_programs(pool, ranks, dtype, F: int, device):
    """Plan every draw of ``pool`` and compile, for ``device``, the pass
    of rank ``ranks[d]`` over draw ``d``'s dispatch and combine plans.
    Returns the plans and the programs, each a pair per draw."""
    import jax
    import jax.numpy as jnp

    from repro.core import jax_collectives as jc
    from repro.tuner import PlannerService

    svc = PlannerService(mesh=None, quantum=1)
    sharding = jax.sharding.SingleDeviceSharding(device)
    all_plans, all_progs = [], []
    for S in pool:
        plans = tuple(svc.plan_record("alltoallv", M, dtype=dtype.name,
                                      row_bytes=F * dtype.itemsize).plan
                      for M in (S, S.T))
        all_plans.append(plans)
        all_progs.append(tuple(
            jax.jit(_pass(jc._slab_ops(), plan.steps), donate_argnums=0)
            .lower(jax.ShapeDtypeStruct((plan.buf_rows, F), dtype,
                                        sharding=sharding),
                   jax.ShapeDtypeStruct((), jnp.int32)).compile()
            for plan in plans))
    return all_plans, all_progs


def _pass(ops, steps):
    """The data plane of ``_apply_steps`` for ``steps`` at traced rank
    ``r``, with no exchange: each step merges the slab the rank itself
    extracted."""
    import jax.numpy as jnp

    extract, merge, step, view = ops

    def run(buf, r):
        shape = buf.shape
        buf = view(buf)
        _, payload0, send0, _, _ = steps[0]
        out = extract(buf, jnp.asarray(send0)[r], payload0)
        for k, (_, _, _, recv_start, recv_valid) in enumerate(steps):
            r0 = jnp.asarray(recv_start)[r]
            nv = jnp.asarray(recv_valid)[r]
            if k + 1 < len(steps):
                _, npayload, nsend, _, _ = steps[k + 1]
                buf, out = step(buf, out, r0, nv, jnp.asarray(nsend)[r],
                                npayload)
            else:
                buf = merge(buf, out, r0, nv)
        return buf.reshape(shape)

    return run


def slab_rows(steps, rank: int, buf_rows: int) -> np.ndarray:
    """The plain slab semantics of one pass at ``rank``, on row indices:
    ``out[row]`` is the row of the buffer before the pass that row ``row``
    holds after it.  Each step copies the ``payload``-row slab at the send
    offset, then writes its ``valid``-row prefix at the receive offset."""
    idx = np.arange(buf_rows, dtype=np.int64)
    _, payload, send, _, _ = steps[0]
    slab = idx[send[rank]: send[rank] + payload].copy()
    for k, (_, _, _, recv, valid) in enumerate(steps):
        n = int(valid[rank])
        idx[recv[rank]: recv[rank] + n] = slab[:n]
        if k + 1 < len(steps):
            _, payload, send, _, _ = steps[k + 1]
            slab = idx[send[rank]: send[rank] + payload].copy()
    return idx


def _compose(one: np.ndarray, times: int) -> np.ndarray:
    """The row map of ``times`` passes, each of row map ``one``."""
    out = np.arange(len(one), dtype=np.int64)
    base = one
    while times:
        if times & 1:
            out = out[base]
        base = base[base]
        times >>= 1
    return out.astype(np.int32)
