"""Shared benchmark plumbing: the algorithm zoo under the alpha-beta model
(paper calibration: QDR InfiniBand, units = MPI_INT as in the tables) and
the CSV emitter (`name,us_per_call,derived`)."""
from __future__ import annotations

import sys

from repro.core import CostParams, allreduce_time, baselines, \
    build_gather_tree, simulate_gather
from repro.core import extensions as ext
from repro.core.distributions import NAMES, block_sizes
from repro.core.guidelines import regular_gather_time

# Calibrated so TUW_Gatherv magnitudes land near the paper's Tables 1-6:
# alpha ~ 1.8us startup, beta ~ 1.4ns per 4-byte int.
PARAMS = CostParams.infiniband_qdr()

SIZES_B = (1, 10, 100, 1_000, 10_000)


def gatherv_times(m, root, params=PARAMS):
    """All gatherv algorithms on one problem.  Times in us."""
    out = {}
    tuw = build_gather_tree(m, root=root)
    out["tuw"] = ext.simulate_gather_overlapped_construction(tuw, params)
    out["tuw_serial"] = simulate_gather(tuw, params,
                                        include_construction=True)
    out["linear"] = simulate_gather(baselines.linear_tree(m, root), params)
    out["binomial"] = simulate_gather(baselines.binomial_tree(m, root),
                                      params)
    out["knomial3"] = simulate_gather(baselines.knomial_tree(m, root, 3),
                                      params)
    # the Intel-MPI library flavor (linear intra + binomial leaders): the
    # paper's Tables 7-11 baseline, NOT this repo's TUW-in-TUW two_level
    out["two_level"] = simulate_gather(
        baselines.two_level_library_tree(m, root, 16), params)
    return out


def gather_regular(p, per_block, root, params=PARAMS):
    """MPI_Gather analog: binomial tree on equal blocks."""
    return regular_gather_time(p, per_block, root, params)


def guideline2_rhs(m, root, params=PARAMS):
    return (allreduce_time(len(m), 1, params)
            + regular_gather_time(len(m), max(m), root, params))


def emit(rows, file=sys.stdout):
    """CSV per harness contract: name,us_per_call,derived."""
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}", file=file)


def moe_load_fractions(p: int, shape: str, seed: int = 0):
    """The canonical MoE expert-load shapes used by the fast-path bench,
    the e2e bench, and the tests — ONE definition so they all validate
    the same matrices.  ``uniform``: balanced; ``single_hot``: one expert
    takes half the traffic; ``zipf``: loads ~ 1/rank^1.2, shuffled."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if shape == "uniform":
        return np.full(p, 1.0 / p)
    if shape == "single_hot":
        frac = np.full(p, 0.5 / (p - 1))
        frac[min(3, p - 1)] = 0.5
        return frac
    if shape == "zipf":
        w = 1.0 / np.arange(1, p + 1) ** 1.2
        return rng.permutation(w / w.sum())
    raise ValueError(shape)


def moe_dispatch_matrix(p: int, tokens: int, shape: str,
                        seed: int = 0):
    """S[i][j]: token rows shard ``i`` sends to expert ``j`` — each
    expert's load split as evenly as possible over the p source shards
    (every expert serves at least one token)."""
    import numpy as np

    S = np.zeros((p, p), np.int64)
    for j, f in enumerate(moe_load_fractions(p, shape, seed)):
        tj = max(1, int(f * tokens))
        base, rem = divmod(tj, p)
        S[:, j] = base
        S[:rem, j] += 1
    return S


def serve_trace(p: int, steps: int, seed: int = 0, *, base_qps: float = 64.0,
                diurnal_amp: float = 0.8, period: int | None = None,
                max_batch: int = 256, mean_decode_len: int = 48,
                prompt_len_range: tuple[int, int] = (8, 512),
                top_k: int = 2, expert_drift: float = 0.02):
    """Deterministic serving trace: diurnal QPS + continuous batching +
    per-step top-k expert routing — ONE seeded generator shared by
    ``benchmarks/serve_bench.py``, the steady-state churn test
    (``tests/test_serving.py``), and ``examples/serve_lm.py``, so bench
    rows are reproducible run-to-run.

    Dynamics per decode step ``t``:

    * arrivals ~ Poisson(rate(t)) with a sinusoidal diurnal rate
      ``base_qps·(1 + diurnal_amp·sin(2πt/period))`` (one step = one
      scheduler tick); each arrival gets a ragged prompt length
      log-uniform in ``prompt_len_range`` and joins the active set,
      capped at ``max_batch`` (overflow waits in queue);
    * each active request finishes with probability
      ``1/mean_decode_len`` per step (geometric decode lengths);
    * every active request contributes ``top_k`` routed rows; expert
      popularity is a slowly rotating zipf (``expert_drift`` controls
      the rotation rate), so the load shape drifts the way diurnal
      production traffic does.

    Returns a list of ``steps`` dicts: ``step``, ``active`` (batch),
    ``arrivals``, ``queued``, ``prompt_lens`` (this step's admissions),
    ``n`` (per-shard routed row counts, shard = request slot mod p) and
    ``S`` (p×p dispatch matrix, ``S[i][j]`` = rows shard i sends expert
    j; ``sum(S) == top_k·active``).
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    period = int(period or max(8, steps // 2))
    lo, hi = prompt_len_range
    active: list[int] = []       # per-request shard ids
    queued: list[int] = []
    zipf = 1.0 / np.arange(1, p + 1) ** 1.1
    order = rng.permutation(p)
    out = []
    slot = 0
    for t in range(int(steps)):
        rate = base_qps * (1.0 + diurnal_amp
                           * np.sin(2.0 * np.pi * t / period))
        arrivals = int(rng.poisson(max(0.0, rate)))
        plens = np.exp(rng.uniform(np.log(lo), np.log(hi + 1),
                                   arrivals)).astype(np.int64)
        for _ in range(arrivals):
            queued.append(slot % p)
            slot += 1
        # completions, then admissions up to the batch cap
        keep = rng.random(len(active)) >= 1.0 / mean_decode_len
        active = [s for s, k in zip(active, keep) if k]
        while queued and len(active) < max_batch:
            active.append(queued.pop(0))
        # slow expert-popularity drift: rotate the zipf assignment
        if expert_drift > 0 and rng.random() < expert_drift * p:
            order = np.roll(order, 1)
        w = zipf[np.argsort(order)]
        w = w / w.sum()
        S = np.zeros((p, p), np.int64)
        n = np.zeros(p, np.int64)
        if active:
            shards = np.asarray(active, np.int64)
            for _ in range(top_k):
                experts = rng.choice(p, size=len(active), p=w)
                np.add.at(S, (shards, experts), 1)
            n = S.sum(axis=1)
        out.append({"step": t, "active": len(active),
                    "arrivals": arrivals, "queued": len(queued),
                    "prompt_lens": plens, "n": n, "S": S})
    return out


def ragged_moe_problem(p: int, tokens: int, shape: str, seed: int = 0):
    """(n, S) for the fwd+bwd bench: ``n[i]`` ragged per-shard token
    counts (the same canonical load shape applied to the data-parallel
    axis — real batches are ragged after packing/filtering) and
    ``S[i][j]`` shard ``i``'s rows for expert ``j`` (largest-remainder
    split of ``n[i]`` over the expert-load fractions, so every row sums
    back to ``n[i]``).  ``uniform`` stays fully balanced on both axes."""
    import numpy as np

    ef = moe_load_fractions(p, shape, seed)
    sf = moe_load_fractions(p, shape, seed + 1)  # decorrelated raggedness
    n = np.maximum(1, (sf * tokens).astype(np.int64))
    S = np.zeros((p, p), np.int64)
    for i in range(p):
        row = np.floor(ef * n[i]).astype(np.int64)
        order = np.argsort(-(ef * n[i] - row))
        row[order[: int(n[i] - row.sum())]] += 1
        S[i] = row
    return n, S
