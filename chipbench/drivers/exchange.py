"""One MoE layer's token exchange over the chips of one host.

One exchange is what an expert-parallel layer waits on: the host plan
lookups for dispatch and combine (``PlannerService.plan_record``), the
dispatch ``alltoallv(S)``, then the combine ``alltoallv(S^T)`` on the
dispatch's output, ending in ``block_until_ready``.  Closed loop, one
exchange in flight.

Program entries driven: ``repro.tuner.PlannerService.plan_record`` and
``jax.jit(jax.shard_map(repro.core.jax_collectives.alltoallv_shard))``.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import routing
from chipbench.drivers import annotation

E2E = {"exchange_ms": "ms", "exchange_p95_ms": "ms"}


class Driver:
    def __init__(self, config: dict, traffic: dict, devices, seed: int, *,
                 trace: bool):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P

        self.p = len(devices)
        if self.p != config["expert_parallel_chips"]:
            raise ValueError(f"{config['name']} spreads its experts over "
                             f"{config['expert_parallel_chips']} chips; "
                             f"{self.p} given")
        self.F = int(config["hidden_size"])
        self.dtype = jnp.dtype(config["dtype"])
        self.row_bytes = self.F * self.dtype.itemsize
        self.mesh = jax.make_mesh((self.p,), ("x",), devices=devices,
                                  axis_types=(AxisType.Auto,))
        self.pool = routing.routing_pool(config, traffic)
        self.order = routing.cycle_order(len(self.pool), seed)
        self.key = routing.payload_key(seed)
        self.cap = int(self.pool[0].sum(axis=1).max())
        if any(int(S.sum(axis=1).min()) != self.cap for S in self.pool):
            raise ValueError("every chip must send the same number of rows")

        self.svc, self.fns, self.plans = compile_programs(
            self.mesh, self.pool, self.dtype, self.F)

        def make(key):
            def body(_):
                i = jax.lax.axis_index("x")
                return routing.payload(jax.random.fold_in(key, i),
                                       (self.cap, self.F), self.dtype)
            return jax.shard_map(body, mesh=self.mesh, in_specs=P("x"),
                                 out_specs=P("x"))(jnp.zeros((self.p,)))

        self.x = jax.jit(make)(self.key)
        self.last: dict[int, tuple] = {}
        self.ran: list[int] = []
        self.plan_s: list[float] = []
        for d in range(len(self.pool)):   # first run of every program
            self.step(d, draw=d)
        self.ran.clear()
        self.plan_s.clear()
        self.xla = self._compile_xla() if trace else None

    # --------------------------------------------------------------- timed
    def _lookup(self, M):
        return lookup(self.svc, M, self.dtype, self.row_bytes)

    def step(self, i: int, *, draw: int | None = None,
             annotate: bool = False) -> None:
        """One exchange of draw ``order[i]``, ending in
        ``block_until_ready``."""
        d = int(self.order[i % len(self.order)]) if draw is None else draw
        S = self.pool[d]
        span = annotation(annotate)
        with span("plan"):
            t0 = time.perf_counter()
            recd, recc = self._lookup(S), self._lookup(S.T)
            t1 = time.perf_counter()
        with span("launch"):
            y = self.fns[recd.serial](self.x)
            z = self.fns[recc.serial](y)
        with span("wait"):
            z.block_until_ready()
        self.plan_s.append(t1 - t0)
        self.ran.append(d)
        self.last[d] = (y, z)

    @staticmethod
    def end_to_end(window_s: float, times: list[float]) -> dict:
        return {"exchange_ms": 1e3 * window_s / len(times),
                "exchange_p95_ms": 1e3 * float(np.percentile(times, 95))}

    # -------------------------------------------------------------- traced
    def segments(self, units: int) -> dict:
        """Traced segments: ``lib`` runs ``units`` exchanges (a multiple of
        the pool) with host spans; ``xla`` runs XLA's ``all_to_all`` at
        capacity on the same draws, dispatch then combine."""
        n = len(self.pool)
        units = max(2 * n, -(-units // n) * n)
        draws = [int(self.order[i % n]) for i in range(units)]

        def lib():
            for i in range(units):
                self.step(i, annotate=True)

        def xla():
            span = annotation(True)
            for d in draws:
                with span("launch"):
                    out = self.xla[d][0](self.xla[d][1])
                with span("wait"):
                    out.block_until_ready()

        return {"lib": (lib, draws), "xla": (xla, draws)}

    def _compile_xla(self):
        """Per draw: the input padded to capacity ``max(S)`` per block,
        laid out on the device, and XLA's exchange of it (two
        ``all_to_all``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        out = []
        for S in self.pool:
            C = int(S.max())
            offs = np.concatenate([np.zeros((self.p, 1), np.int64),
                                   np.cumsum(S, axis=1)[:, :-1]], axis=1)
            offs_t, sizes_t = jnp.asarray(offs, jnp.int32), jnp.asarray(
                S, jnp.int32)

            def pad(xl, offs_t=offs_t, sizes_t=sizes_t, C=C):
                i = jax.lax.axis_index("x")
                xl = jnp.concatenate([xl, jnp.zeros((C, self.F), xl.dtype)])
                rows = jnp.arange(C)[:, None]
                return jnp.stack([
                    jnp.where(rows < sizes_t[i, j], jax.lax.dynamic_slice(
                        xl, (offs_t[i, j], 0), (C, self.F)), 0)
                    for j in range(self.p)])

            xp = jax.jit(jax.shard_map(pad, mesh=self.mesh, in_specs=P("x"),
                                       out_specs=P("x")))(self.x)

            def a2a(v):
                v = jax.lax.all_to_all(v, "x", 0, 0, tiled=True)
                return jax.lax.all_to_all(v, "x", 0, 0, tiled=True)

            fn = jax.jit(jax.shard_map(a2a, mesh=self.mesh, in_specs=P("x"),
                                       out_specs=P("x"))).lower(xp).compile()
            fn(xp).block_until_ready()
            out.append((fn, xp))
        return out

    def layer_context(self) -> dict:
        return {"row_bytes": self.row_bytes, "pool": self.pool,
                "plans": self.plans, "ran": list(self.ran),
                "plan_s": list(self.plan_s)}

    # ------------------------------------------------------------- correct
    def free(self) -> None:
        """Drop all state but the answers kept for the check."""
        self.x = None
        self.xla = None
        self.fns.clear()

    def check(self, precision: str | None = None) -> dict:
        """Mismatched elements of the last exchange of every draw, on every
        chip, dispatch and combine, against a plain reference that
        regenerates the payload from the seed and moves rows by index.

        ``precision="float8_e4m3fn"`` computes the reference through that
        type: the control, which has to come out wrong."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        p, cap, F, dtype, key = self.p, self.cap, self.F, self.dtype, self.key
        bad = {"dispatch": 0, "combine": 0}
        wrong = 0
        for d, S in enumerate(self.pool):
            if d not in self.last:
                continue
            y, z = self.last[d]
            src, valid = _dispatch_rows(S, cap)

            def body(key, y_l, z_l, src_l, valid_l):
                j = jax.lax.axis_index("x")
                xs = [routing.payload(jax.random.fold_in(key, i), (cap, F),
                                      dtype) for i in range(p)]
                if precision:
                    xs = [routing.lowered(x, precision) for x in xs]
                want = jnp.concatenate(xs)[src_l[0]]
                rows = jnp.arange(src_l.shape[1])[:, None]
                got = y_l[: src_l.shape[1]]
                disp = jnp.sum((routing.as_bits(got) != routing.as_bits(want))
                               & (rows < valid_l[0]))
                own = jnp.stack(xs)[j]
                comb = jnp.sum(routing.as_bits(z_l[:cap])
                               != routing.as_bits(own))
                return jnp.stack([disp, comb]).astype(jnp.int32)[None]

            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(P(),) + (P("x"),) * 4,
                out_specs=P("x")))
            counts = np.asarray(fn(key, y, z, jnp.asarray(src),
                                   jnp.asarray(valid)))
            bad["dispatch"] += int(counts[:, 0].sum())
            bad["combine"] += int(counts[:, 1].sum())
            wrong += int((counts[:, 0] > 0).any()) + int((counts[:, 1] > 0)
                                                         .any())
            del y, z
        self.last.clear()
        return {"checks": {"dispatch_mismatched_elements":
                           (bad["dispatch"], 0),
                           "combine_mismatched_elements":
                           (bad["combine"], 0)},
                "answers": 2 * len(self.pool), "wrong": wrong}


def lookup(svc, M, dtype, row_bytes: int):
    return svc.plan_record("alltoallv", M, dtype=dtype.name,
                           row_bytes=row_bytes)


def compile_programs(mesh, pool, dtype, F: int):
    """Plan every draw of ``pool`` (dispatch ``S`` and combine ``S^T``) and
    compile its executor programs for ``mesh``.  Returns the planner, the
    compiled programs keyed by plan serial, and the plans per draw."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import jax_collectives as jc
    from repro.tuner import PlannerService

    p = mesh.devices.size
    spec = NamedSharding(mesh, P("x"))
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=1)
    fns, plans = {}, []
    for S in pool:
        rows = int(S.sum(axis=1).max())
        pair = []
        for M in (S, S.T):
            rec = lookup(svc, M, dtype, F * dtype.itemsize)
            if rec.serial not in fns:
                plan = rec.plan
                fns[rec.serial] = jax.jit(jax.shard_map(
                    lambda xl, plan=plan: jc.alltoallv_shard(
                        xl[: plan.cap], plan, "x"),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                    check_vma=False)).lower(jax.ShapeDtypeStruct(
                        (p * rows, F), dtype, sharding=spec)).compile()
            pair.append(rec.plan)
            rows = rec.plan.out_rows
        plans.append(tuple(pair))
    return svc, fns, plans


def _dispatch_rows(S, cap: int):
    """Per receiving chip ``j``: the flat source row (``i * cap`` + offset
    in chip ``i``'s input) of each row it receives, in source order, and
    how many it receives.  The plain reference of ``alltoallv``."""
    S = np.asarray(S, np.int64)
    p = len(S)
    offs = np.concatenate([np.zeros((p, 1), np.int64),
                           np.cumsum(S, axis=1)[:, :-1]], axis=1)
    L = int(S.sum(axis=0).max())
    src = np.zeros((p, L), np.int32)
    for j in range(p):
        rows = [i * cap + offs[i, j] + np.arange(S[i, j]) for i in range(p)]
        flat = np.concatenate(rows) if rows else np.zeros(0, np.int64)
        src[j, : len(flat)] = flat
    return src, S.sum(axis=0).astype(np.int32)[:, None]
