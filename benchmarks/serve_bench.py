"""Serving-scale dataplane bench: recompile-free continuous batching at
high QPS (the ROADMAP serving target).

A seeded diurnal request trace (``benchmarks.common.serve_trace`` —
Poisson arrivals with a sinusoidal rate, ragged prompt lengths,
geometric decode lengths, per-step top-k expert routing with drifting
zipf popularity) streams through a :class:`ServingPlanner` over a
quantum=1 :class:`PlannerService`.  Every decode step plans the MoE
dispatch (alltoallv on the routed size matrix) and combine
(reduce_scatterv on the per-shard row counts) through signature
classes, then prefetches the predicted next classes off the hot path.

Two lanes:

* **planner lane** (device-free) — per-step plan latencies on the
  synthetic true machine, vs the static padded-alltoall BASELINE
  (one direct pairwise all-to-all + one recursive-halving
  reduce-scatter provisioned at the trace-wide maximum — what a
  recompile-free server gets WITHOUT signature classes: worst-case
  capacity every step).  Steady state is the longest replan-free run of
  decode steps; the lane asserts it spans ≥ ``STEADY_TARGET`` steps
  with ZERO hot-path plan-cache misses, zero compiles (plan-only
  service), and priced padding overhead ≤ the class bound.  Reports
  sustained steps/s and p50/p99 step latency for both paths, plus the
  hot plan-path wall cost (classify + cache hit) per step.

* **exec lane** (runs when ≥ 4 JAX devices are available; otherwise it
  adds an ``exec_not_run`` row saying why) — payloads REALLY flow
  through the compiled executables on a 4-device mesh, over the slab
  data plane ``jax_collectives.set_dataplane`` selected (the compiled
  Pallas kernels unless the caller chose otherwise; on forced CPU
  devices choose ``"xla"`` or ``"interpret"``):
  per-step wall-clock latencies, and the recompile-free assertion on
  the honest XLA counter (the service's compiled-LRU misses — each
  miss jits one executable): ZERO new compiles after warmup.

Writes ``results/serve_bench.json`` (schema: EXPERIMENTS.md §Serve
bench):

    PYTHONPATH=src python benchmarks/serve_bench.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):  # direct-script execution
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_REPO, os.path.join(_REPO, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)
    from benchmarks.common import emit, serve_trace
    from benchmarks.moe_e2e import measure_plan
else:
    from .common import emit, serve_trace
    from .moe_e2e import measure_plan

from repro.core.costmodel import CostParams
from repro.core.jax_collectives import DATAPLANES, dataplane, set_dataplane
from repro.tuner import (PlannerService, ServingPlanner,
                         SyntheticTimingBackend)

RESULTS = os.path.join(os.environ.get("REPRO_RESULTS", os.getcwd()),
                       "results")

P = 8                      # expert shards
ROW_BYTES = 512            # d_model=128 float32 activation rows
STEPS = 1500               # decode steps replayed
STEADY_TARGET = 500        # the replan-free run must span at least this
BOUND = 0.25               # signature-class padding overhead bound
TRACE = dict(base_qps=8.0, diurnal_amp=0.6, period=128, max_batch=1024,
             mean_decode_len=48, top_k=4)


def _percentiles(xs) -> dict:
    arr = np.asarray(xs, np.float64)
    return {"p50": float(np.percentile(arr, 50)),
            "p99": float(np.percentile(arr, 99)),
            "mean": float(arr.mean())}


def _longest_zero_run(miss_steps: list[int], steps: int) -> tuple[int, int]:
    """(start, length) of the longest run of steps with no hot miss."""
    pts = [-1] + sorted(miss_steps) + [steps]
    best = (0, 0)
    for a, b in zip(pts, pts[1:]):
        if b - a - 1 > best[1]:
            best = (a + 1, b - a - 1)
    return best


def _baseline_plans(trace):
    """The static padded-alltoall pair: provisioned once at the
    trace-wide maxima, reused every step — recompile-free by
    construction, paying worst-case capacity instead of classes."""
    from repro.core.composed import (alltoallv_direct_schedule,
                                     reduce_scatterv_halving_schedule)
    from repro.core.jax_collectives import (plan_alltoallv,
                                            plan_reduce_scatterv)

    cap = max(int(st["S"].max()) for st in trace)
    ncap = max(int(st["n"].max()) for st in trace)
    pad = np.full((P, P), cap, np.int64)
    pad_n = [ncap] * P
    a2a = plan_alltoallv(pad, validate=False,
                         schedule=alltoallv_direct_schedule(pad))
    rs = plan_reduce_scatterv(pad_n, validate=False,
                              schedule=reduce_scatterv_halving_schedule(
                                  pad_n))
    return a2a, rs, cap, ncap


def planner_lane(rows: list, seed: int = 4) -> dict:
    trace = serve_trace(P, STEPS, seed=seed, **TRACE)
    machine = SyntheticTimingBackend(alpha_s=2e-6, beta_s_per_byte=2.5e-11,
                                     noise=0.03, seed=11)
    svc = PlannerService(mesh=None, quantum=1, params=CostParams.tpu_ici(),
                         max_cached_plans=1024)
    serving = ServingPlanner(svc, max_overhead=BOUND, row_bytes=ROW_BYTES)
    base_a2a, base_rs, cap, ncap = _baseline_plans(trace)

    fast_s, base_s, plan_wall_s, miss_steps = [], [], [], []
    for st in trace:
        misses0 = serving.hot_misses
        t0 = time.perf_counter()
        disp = serving.plan_step("alltoallv", st["S"], row_bytes=ROW_BYTES)
        comb = serving.plan_step("reduce_scatterv",
                                 [int(v) for v in st["n"]],
                                 row_bytes=ROW_BYTES)
        plan_wall_s.append(time.perf_counter() - t0)
        serving.prefetch()          # off the hot path: predicted classes
        if serving.hot_misses > misses0:
            miss_steps.append(st["step"])
        fast_s.append(measure_plan(disp.plan, machine, ROW_BYTES)
                      + measure_plan(comb.plan, machine, ROW_BYTES))
        base_s.append(measure_plan(base_a2a, machine, ROW_BYTES)
                      + measure_plan(base_rs, machine, ROW_BYTES))

    start, length = _longest_zero_run(miss_steps, STEPS)
    stats = serving.stats()
    # acceptance: a replan-free steady state of >= STEADY_TARGET decode
    # steps, zero compiles (plan-only service), overhead within bound
    assert length >= STEADY_TARGET, (length, start, miss_steps)
    assert stats["compiles"] == 0, stats
    assert stats["overhead_max"] <= BOUND + 1e-12, stats
    sl = slice(start, start + length)
    fast = _percentiles(fast_s[sl])
    base = _percentiles(base_s[sl])
    plan_wall = _percentiles(plan_wall_s[sl])
    fast["steps_per_s"] = 1.0 / fast["mean"]
    base["steps_per_s"] = 1.0 / base["mean"]
    speedup = base["mean"] / fast["mean"]
    rows.append(("serve_bench/steady_state", fast["mean"] * 1e6,
                 f"steps_per_s={fast['steps_per_s']:.0f};"
                 f"p50_us={fast['p50'] * 1e6:.1f};"
                 f"p99_us={fast['p99'] * 1e6:.1f};"
                 f"steady_steps={length};hot_misses=0;compiles=0;"
                 f"speedup_vs_padded={speedup:.2f}x"))
    rows.append(("serve_bench/padded_baseline", base["mean"] * 1e6,
                 f"steps_per_s={base['steps_per_s']:.0f};"
                 f"p50_us={base['p50'] * 1e6:.1f};"
                 f"p99_us={base['p99'] * 1e6:.1f};"
                 f"cap={cap};ncap={ncap}"))
    rows.append(("serve_bench/hot_plan_path", plan_wall["mean"] * 1e6,
                 f"p50_us={plan_wall['p50'] * 1e6:.1f};"
                 f"p99_us={plan_wall['p99'] * 1e6:.1f};"
                 f"classes={stats['classes']};"
                 f"prefetch_hits={stats['prefetch_hits']};"
                 f"overhead_max={stats['overhead_max']:.3f}"))
    return {"seed": seed, "steps": STEPS, "trace": TRACE,
            "steady": {"start": start, "length": length,
                       "target": STEADY_TARGET,
                       "fast": fast, "baseline": base,
                       "plan_path_wall": plan_wall,
                       "speedup_vs_padded": speedup},
            "miss_steps": miss_steps, "planner": stats,
            "baseline_caps": {"alltoallv_entry": cap,
                              "reduce_scatterv_entry": ncap}}


# --------------------------------------------------------------------------
# exec lane: real payloads through compiled executables on a host mesh
# --------------------------------------------------------------------------

EXEC_P = 4
EXEC_F = 8
EXEC_STEPS = 120
EXEC_WARMUP = 40


def exec_lane(rows: list, seed: int = 3) -> dict:
    import jax

    if jax.device_count() < EXEC_P:
        reason = f"device_count={jax.device_count()} < {EXEC_P}"
        rows.append(("serve_bench/exec_not_run", 0.0, f"reason={reason}"))
        print(f"# serve_bench exec lane did NOT run: {reason}",
              file=sys.stderr)
        return {"not_run": reason}
    mesh = jax.make_mesh((EXEC_P,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=1,
                         max_cached_plans=512, max_compiled=256)
    serving = ServingPlanner(svc, max_overhead=BOUND,
                             row_bytes=EXEC_F * 4)
    trace = serve_trace(EXEC_P, EXEC_STEPS, seed=seed, base_qps=12.0,
                        diurnal_amp=0.5, period=32, max_batch=256,
                        mean_decode_len=16, top_k=2)
    rng = np.random.default_rng(seed)
    wall_s = []
    marks = {}
    for st in trace:
        S = st["S"]
        n = [int(v) for v in st["n"]]
        blocks = [[rng.standard_normal((int(S[i, j]), EXEC_F))
                   .astype(np.float32) for j in range(EXEC_P)]
                  for i in range(EXEC_P)]
        contribs = [rng.standard_normal((sum(n), EXEC_F))
                    .astype(np.float32) for _ in range(EXEC_P)]
        t0 = time.perf_counter()
        recv, _ = serving.dispatch(blocks)
        outs, _ = serving.combine(contribs, n)
        wall_s.append(time.perf_counter() - t0)
        serving.prefetch(compile_width=EXEC_F)   # pre-jit predicted rungs
        if st["step"] == EXEC_WARMUP - 1:
            marks = {"compiles": svc.compiled_misses,
                     "hot_misses": serving.hot_misses}
        # spot-check exactness on the true rows (class padding strips)
        for j in range(EXEC_P):
            want = np.concatenate([blocks[i][j] for i in range(EXEC_P)]
                                  ) if S[:, j].sum() else recv[j]
            assert recv[j].shape[0] == int(S[:, j].sum()), (j, st["step"])
            np.testing.assert_array_equal(recv[j], want[:recv[j].shape[0]])
    # the honest recompile-free claim: the XLA jit counter did not move
    # after warmup, and neither did the hot plan path
    new_compiles = svc.compiled_misses - marks["compiles"]
    new_misses = serving.hot_misses - marks["hot_misses"]
    assert new_compiles == 0, (marks, svc.compiled_misses)
    assert new_misses == 0, (marks, serving.hot_misses)
    steady = _percentiles(wall_s[EXEC_WARMUP:])
    stats = serving.stats()
    rows.append(("serve_bench/exec_steady", steady["mean"] * 1e6,
                 f"p50_us={steady['p50'] * 1e6:.0f};"
                 f"p99_us={steady['p99'] * 1e6:.0f};"
                 f"devices={EXEC_P};steady_steps={EXEC_STEPS - EXEC_WARMUP};"
                 f"xla_recompiles=0;compiles_total={stats['compiles']};"
                 f"dataplane={dataplane()}"))
    return {"seed": seed, "devices": EXEC_P, "dataplane": dataplane(),
            "steps": EXEC_STEPS,
            "warmup": EXEC_WARMUP, "steady_wall": steady,
            "compiles_total": stats["compiles"],
            "steady_new_compiles": new_compiles,
            "steady_new_hot_misses": new_misses,
            "planner": stats}


def run(emit_rows: bool = True, out_path: str | None = None):
    rows: list = []
    planner = planner_lane(rows)
    exec_info = exec_lane(rows)
    payload = {
        "version": 1,
        "config": {"p": P, "row_bytes": ROW_BYTES, "steps": STEPS,
                   "steady_target": STEADY_TARGET, "class_bound": BOUND},
        "planner_lane": planner,
        "exec_lane": exec_info,
        "planner": planner["planner"],
    }
    if out_path is None:
        out_path = os.path.join(RESULTS, "serve_bench.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    if emit_rows:
        emit(rows)
        print(f"# wrote {out_path}", file=sys.stderr)
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="JSON output path (default results/serve_bench.json)")
    ap.add_argument("--dataplane", choices=DATAPLANES, default="pallas",
                    help="slab data plane of the exec lane: compiled "
                    "'pallas' (TPU), or 'interpret' / 'xla' on CPU devices")
    args = ap.parse_args()
    set_dataplane(args.dataplane)
    print("name,us_per_call,derived")
    run(out_path=args.out)


if __name__ == "__main__":
    main()
