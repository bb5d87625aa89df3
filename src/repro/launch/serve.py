"""Batched serving driver: prefill a batch of prompts, then decode with a
simple continuous-batching loop (finished sequences are replaced by
queued requests; the ragged prompt lengths feed the scatterv path).

The decode loop's MoE edges go through the serving dataplane: each
step's top-k expert routing becomes an alltoallv dispatch + a
reduce_scatterv combine planned through
:class:`~repro.tuner.serving.ServingPlanner` — raw per-step size
vectors collapse onto padded signature classes, so the steady-state
loop replans (and recompiles) nothing.  Per-step spans feed the
``repro.obs`` trace plane (run under ``REPRO_TRACE=1`` and export with
``--trace-out``).

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --reduced --requests 8 --prompt-len 24 --gen 16 --experts 4
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import init_cache, init_params
from repro.obs import trace as obs_trace
from repro.train import make_decode_step, make_prefill_step
from repro.tuner import PlannerService, ServingPlanner


def pop_batch(queue: list, batch: int) -> list:
    """Drain up to ``batch`` requests off the queue head.

    Never pops more than ``len(queue)`` items: the old
    ``min(batch, len(queue) + 1)`` drained one item too many and raised
    IndexError whenever the remaining queue was smaller than the batch
    (e.g. ``--requests 6 --batch 4``).
    """
    take = min(int(batch), len(queue))
    return [queue.pop(0) for _ in range(take)]


def route_step(tokens: np.ndarray, experts: int, top_k: int,
               step: int) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic per-step top-k routing of the current batch tokens.

    Batch slot ``b`` lives on shard ``b % experts``; its ``top_k``
    experts are a hash of (token id, step, slot) — distinct per token —
    so the dispatch matrix churns every decode step exactly like a
    learned router's output does.  Returns ``(S, n)``: ``S[i][j]`` rows
    shard i sends expert j, ``n[i]`` rows leaving shard i.
    """
    p = int(experts)
    S = np.zeros((p, p), np.int64)
    for b, tok in enumerate(np.asarray(tokens).reshape(-1)):
        shard = b % p
        h = (int(tok) * 2654435761 + step * 97 + b) % (1 << 32)
        first = h % p
        for k in range(top_k):
            S[shard, (first + k * max(1, h % (p - 1) if p > 1 else 1)) % p] \
                += 1
    return S, S.sum(axis=1)


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="recurrentgemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--experts", type=int, default=4,
                    help="virtual MoE shard/expert count for the "
                         "dispatch/combine planning (0 = off)")
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--class-bound", type=float, default=0.25,
                    help="signature-class padding overhead bound")
    ap.add_argument("--trace-replay", action="store_true",
                    help="draw request arrivals from the shared seeded "
                         "diurnal trace (benchmarks.common.serve_trace)")
    ap.add_argument("--trace-out", default=None,
                    help="write the obs trace (Chrome-trace JSON) here")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.with_(dtype="float32")
    assert cfg.embed_inputs, "serve demo uses token archs"
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg))

    recorder = None
    if args.trace_out is not None and obs_trace.current() is None:
        recorder = obs_trace.enable(obs_trace.TraceRecorder())

    # request queue with ragged prompt lengths (irregular scatter pattern)
    if args.trace_replay:
        # the shared deterministic fixture: prompt lengths come from the
        # diurnal trace's admissions, clamped to the demo's prompt cap
        from benchmarks.common import serve_trace

        plens: list[int] = []
        for step in serve_trace(max(2, args.experts or 4), steps=64, seed=0,
                                base_qps=max(1.0, args.requests / 8),
                                prompt_len_range=(max(1, args.prompt_len
                                                      // 2),
                                                  args.prompt_len)):
            plens.extend(int(x) for x in step["prompt_lens"])
            if len(plens) >= args.requests:
                break
        if not plens:
            plens = [args.prompt_len]
        plens = plens * (1 + args.requests // len(plens))
        queue = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                 for n in plens[: args.requests]]
    else:
        queue = [rng.integers(
            0, cfg.vocab,
            rng.integers(args.prompt_len // 2,
                         args.prompt_len + 1)).astype(np.int32)
            for _ in range(args.requests)]

    serving = None
    if args.experts > 0:
        svc = PlannerService(mesh=None, quantum=1)
        serving = ServingPlanner(svc, max_overhead=args.class_bound,
                                 row_bytes=cfg.d_model * 4)

    done = 0
    t0 = time.time()
    tokens_out = 0
    step_id = 0
    row_bytes = cfg.d_model * 4
    while queue:
        batch_prompts = pop_batch(queue, args.batch)
        b = len(batch_prompts)
        plen = max(len(p) for p in batch_prompts)
        toks = np.zeros((b, plen), np.int32)
        for i, p in enumerate(batch_prompts):
            toks[i, plen - len(p):] = p  # left-pad (simple alignment)
        cache = init_cache(cfg, b, plen + args.gen)
        logits, cache = prefill(params, {"tokens": jnp.asarray(toks)}, cache)
        cur = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        for _ in range(args.gen):
            t_step = time.perf_counter()
            logits, cache = decode(params, cache, {"tokens": cur})
            cur = jnp.argmax(logits[:, -1],
                             axis=-1)[:, None].astype(jnp.int32)
            if serving is not None:
                S, n = route_step(np.asarray(cur), args.experts,
                                  args.top_k, step_id)
                serving.plan_step("alltoallv", S, row_bytes=row_bytes)
                serving.plan_step("reduce_scatterv",
                                  [int(v) for v in n],
                                  row_bytes=row_bytes)
                serving.prefetch()     # off the hot path: next classes
            tr = obs_trace.current()
            if tr is not None:
                tr.add_complete("serve/decode_step", "serving", t_step,
                                time.perf_counter() - t_step,
                                step=step_id, batch=b)
            tokens_out += b
            step_id += 1
        done += b
    dt = time.time() - t0
    print(f"served {done} requests, {tokens_out} tokens, "
          f"{tokens_out / dt:.1f} tok/s")
    if serving is not None:
        st = serving.stats()
        print(f"planner: {st['classes']} signature classes over "
              f"{st['steps']} plan steps, {st['plan_hits']} hits / "
              f"{st['plan_misses']} misses, {st['compiles']} compiles, "
              f"prefetch {st['prefetch_hits']}/{st['prefetch_planned']}, "
              f"padding overhead <= {st['overhead_max']:.3f} "
              f"(bound {st['overhead_bound']})")
    if recorder is not None:
        path = recorder.save(args.trace_out)
        obs_trace.disable()
        print(f"trace written to {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
