"""Segmented pipelining of round-synchronous schedules (beyond-paper layer).

The monolithic data plane serializes a tree's rounds: round ``k`` moves
its whole payload before round ``k+1`` starts, so a plan with ``R``
rounds pays the bandwidth term ``R`` times on the critical buffer instead
of once — the gap between the padded ppermute lowering and the paper's
``3⌈log₂p⌉α + βΣm_i`` bound.  Chunked/pipelined execution over the same
trees is the standard fix (Träff arXiv:1711.08731 §5, NVIDIA PAT
arXiv:2506.20252): split the payload into ``S`` segments and stream them,
so round ``k+1`` of segment ``j`` overlaps round ``k`` of segment
``j+1`` and the whole schedule finishes in ``R + S - 1`` stages of
``~m/S``-sized transfers.

**Segmentation is by GLOBAL row chunk, not per transfer.**  The flat row
space ``[0, total)`` is cut into ``S`` contiguous chunks; the piece of a
round-``k`` transfer that falls in chunk ``j`` is scheduled at stage
``k + j``.  This is the choice that makes the pipeline correct by
construction:

* a row in chunk ``j`` only ever travels in chunk-``j`` pieces, so a
  stage-``k+j`` forward depends only on stages ``k' + j`` with
  ``k' < k`` — strictly earlier stages;
* two pieces in the same stage ``t`` come from different rounds
  ``k ≠ k'`` and therefore different chunks ``t-k ≠ t-k'`` — disjoint
  rows, so there is no intra-stage dependency and the stage's pieces may
  be issued in any wave order;
* every piece is still one contiguous slab at its global flat offset, so
  the zero-copy consecutive-rank-range invariant (and the whole
  ``dynamic_slice`` addressing scheme) survives untouched.

Per-transfer relative segmentation — splitting each transfer's own range
into ``S`` equal parts — does NOT have these properties: a child's range
can sit entirely inside the parent's last segment, so "segment j forwards
segment j" breaks and same-stage ppermutes can carry stale rows.

**Composed alltoallv segments PER TREE, not globally.**  An alltoallv
schedule concatenates ``p`` independent scatter trees' row spaces into
the flat space, so a global ``S``-chunking with ``S < p`` leaves most
trees entirely inside ONE chunk: their transfers are never actually
split, each tree is merely delayed by its chunk index, and the pipeline
pays ``S - 1`` extra stages of startups for no payload reduction — this
is why the flat transform rarely made ``S > 1`` win for alltoallv.
``pipeline_rounds_per_tree`` instead cuts EACH tree's own row span into
``S`` chunks and schedules the piece of a round-``k`` transfer falling
in its tree's chunk ``j`` at stage ``k + j``.  Correctness needs no new
argument: different trees carry disjoint rows (no cross-tree
dependencies at all), and within one tree this IS the global-chunk
transform applied to that tree's row space.  The payoff is cross-tree
stage fusion: at stage ``t``, chunk-``j`` pieces of EVERY tree travel
together and ``_bucketed_steps`` packs them into shared ppermute waves,
so a stage still pays one α per wave while every piece shrank to
``~1/S`` of its transfer.

``pipeline_rounds`` / ``pipeline_rounds_per_tree`` are the whole
transform; the lowering in ``repro.core.jax_collectives`` runs it right
before ``_bucketed_steps``, so legalization, bucketing, and both SPMD
executors are reused verbatim.  ``execute_steps_numpy`` is the
pure-NumPy oracle of the step tables used by the differential tests
(pipelined == monolithic at any ``p`` without devices).
"""
from __future__ import annotations

import bisect

import numpy as np

Transfer4 = tuple[int, int, int, int]  # (src, dst, size, start)


def segment_bounds(total_rows: int, segments: int) -> list[tuple[int, int]]:
    """Cut ``[0, total_rows)`` into ``segments`` contiguous chunks.

    Chunk sizes differ by at most one row (the first ``total % S`` chunks
    are one row larger); zero-row chunks are legal and simply contribute
    no pieces.
    """
    S = int(segments)
    if S < 1:
        raise ValueError("segments >= 1")
    base, rem = divmod(max(0, int(total_rows)), S)
    bounds, lo = [], 0
    for j in range(S):
        hi = lo + base + (1 if j < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def pipeline_rounds(rounds: list[list[Transfer4]], segments: int,
                    total_rows: int) -> list[list[Transfer4]]:
    """Re-time ``rounds`` into ``len(rounds) + segments - 1`` stages.

    ``rounds[k]`` is a list of ``(src, dst, size, start)`` transfers whose
    row ranges live in the flat space ``[0, total_rows)``.  The piece of a
    round-``k`` transfer intersecting global chunk ``j`` is emitted at
    stage ``k + j`` (see module docstring for why this is dependency-safe
    and slab-contiguous).  ``segments == 1`` returns the rounds unchanged
    (shallow copies), so the monolithic path is the ``S=1`` special case.

    Stages that end up empty are kept (as empty lists) so stage indices
    stay aligned with the cost model; the lowering skips them.
    """
    rounds = [list(r) for r in rounds]
    if segments <= 1 or not rounds:
        return rounds
    bounds = segment_bounds(total_rows, segments)
    stages: list[list[Transfer4]] = [
        [] for _ in range(len(rounds) + segments - 1)]
    for k, rnd in enumerate(rounds):
        for src, dst, size, start in rnd:
            a, b = int(start), int(start) + int(size)
            for j, (lo, hi) in enumerate(bounds):
                plo, phi = max(a, lo), min(b, hi)
                if phi > plo:
                    stages[k + j].append((src, dst, phi - plo, plo))
    return stages


def pipeline_rounds_per_tree(rounds: list[list[Transfer4]], segments: int,
                             tree_spans: list[tuple[int, int]]
                             ) -> list[list[Transfer4]]:
    """Re-time ``rounds`` with PER-TREE segmentation (composed alltoallv).

    ``tree_spans`` is a sorted, disjoint list of ``(lo, hi)`` flat row
    spans, one per tree; every transfer's range must lie inside exactly
    one span (composed transfers carry one tree's consecutive block
    range, so this holds by construction).  Each span is cut into
    ``segments`` chunks independently and the piece of a round-``k``
    transfer in its tree's chunk ``j`` is emitted at stage ``k + j`` —
    see the module docstring for why this is dependency-safe and why it
    beats global chunking when the flat space is a concatenation of many
    per-tree spaces.  Stage count is ``len(rounds) + segments - 1``, same
    as the global transform.
    """
    rounds = [list(r) for r in rounds]
    if segments <= 1 or not rounds:
        return rounds
    spans = sorted((int(lo), int(hi)) for lo, hi in tree_spans)
    starts = [lo for lo, _ in spans]
    bounds_per_span = [
        [(lo + a, lo + b) for a, b in segment_bounds(hi - lo, segments)]
        for lo, hi in spans
    ]
    stages: list[list[Transfer4]] = [
        [] for _ in range(len(rounds) + segments - 1)]
    for k, rnd in enumerate(rounds):
        for src, dst, size, start in rnd:
            a, b = int(start), int(start) + int(size)
            i = bisect.bisect_right(starts, a) - 1
            lo, hi = spans[i]
            if not (lo <= a and b <= hi):
                raise ValueError(
                    f"transfer [{a}, {b}) crosses tree span boundaries "
                    f"(span [{lo}, {hi})): per-tree segmentation needs "
                    "span-contained transfers")
            for j, (clo, chi) in enumerate(bounds_per_span[i]):
                plo, phi = max(a, clo), min(b, chi)
                if phi > plo:
                    stages[k + j].append((src, dst, phi - plo, plo))
    return stages


def num_stages(n_rounds: int, segments: int) -> int:
    """Stage count of the pipelined schedule: ``R + S - 1`` (0 if empty)."""
    if n_rounds <= 0:
        return 0
    return n_rounds + max(1, int(segments)) - 1


# --------------------------------------------------------------------------
# NumPy reference executor of lowered step tables (differential oracle)
# --------------------------------------------------------------------------

def execute_steps_numpy(steps, bufs: np.ndarray) -> np.ndarray:
    """Run ppermute step tables over per-device buffers, in NumPy.

    ``bufs``: (p, buf_rows, F) array, one flat row buffer per device.
    Each step is applied with ppermute semantics — every receive reads the
    sender's buffer state from BEFORE the step — exactly mirroring
    ``jax_collectives._apply_steps``.  Returns the final (p, buf_rows, F)
    state.  This lets differential tests compare pipelined vs monolithic
    plans at any ``p`` (64, 4096, ...) without devices.
    """
    bufs = np.array(bufs, copy=True)
    for perm, payload, send_start, recv_start, recv_valid in steps:
        for d, r0, rows in _sent_rows(bufs, perm, send_start, recv_start,
                                      recv_valid):
            bufs[d, r0: r0 + len(rows)] = rows
    return bufs


def _sent_rows(bufs, perm, send_start, recv_start, recv_valid):
    """Copies of every slab one step sends, taken before any lands:
    ppermute semantics, without snapshotting the whole buffer."""
    return [(d, int(recv_start[d]),
             bufs[s, int(send_start[s]): int(send_start[s])
                  + int(recv_valid[d])].copy())
            for s, d in perm]


def execute_alltoallv_plan_numpy(plan, blocks) -> list[np.ndarray]:
    """Run a lowered alltoallv plan end-to-end in NumPy.

    ``blocks[i][j]``: the (S[i][j], F) array rank ``i`` sends to rank
    ``j``.  Packs each device's input row at ``plan.in_starts``, runs the
    step tables through :func:`execute_steps_numpy`, and unpacks with the
    plan's per-tree extract tables.  Returns device ``j``'s received rows
    — ``concat_i blocks[i][j]`` — one (out_valid[j], F) array per device.
    The single host-side oracle of the full alltoallv dataplane, shared
    by the differential tests and ``benchmarks/moe_e2e.py``'s numeric
    leg.
    """
    p = plan.p
    F = blocks[0][0].shape[1]
    dtype = np.result_type(*(b.dtype for row in blocks for b in row))
    bufs = np.zeros((p, plan.buf_rows, F), dtype)
    for i in range(p):
        off = plan.in_starts[i]
        for j in range(p):
            bufs[i, off: off + len(blocks[i][j])] = blocks[i][j]
            off += len(blocks[i][j])
    fin = execute_steps_numpy(plan.steps, bufs)
    out = np.zeros((p, plan.out_rows, F), dtype)
    for src_start, dst_start, valid in plan.extract:
        for i in range(p):
            nv = int(valid[i])
            if nv:
                out[i, dst_start[i]: dst_start[i] + nv] = \
                    fin[i, src_start[i]: src_start[i] + nv]
    return [out[j, : plan.out_valid[j]] for j in range(p)]


def execute_reduce_steps_numpy(steps, bufs: np.ndarray) -> np.ndarray:
    """Run step tables with FUSED-ADD receive semantics, in NumPy.

    Identical to :func:`execute_steps_numpy` except each received slab is
    ADDED into the receiver's rows instead of overwriting them — the
    oracle of ``jax_collectives._apply_steps(..., reduce=True)`` and of
    the ``slab_step_reduce`` kernel.  ppermute snapshot semantics (every
    receive reads sender state from before the step) are what make the
    reduction well-defined: a rank may fold in a partial sum and forward
    its own in the same step without double counting.
    """
    bufs = np.array(bufs, copy=True)
    for perm, payload, send_start, recv_start, recv_valid in steps:
        for d, r0, rows in _sent_rows(bufs, perm, send_start, recv_start,
                                      recv_valid):
            bufs[d, r0: r0 + len(rows)] += rows
    return bufs


def execute_reduce_scatterv_plan_numpy(plan, contribs) -> list[np.ndarray]:
    """Run a lowered reduce_scatterv plan end-to-end in NumPy.

    ``contribs[i]``: rank ``i``'s (total, F) flat contribution vector
    (segment ``j``'s rows at ``plan.offsets[j]``).  Returns rank ``j``'s
    reduced block ``sum_i contribs[i][offsets[j]: offsets[j]+sizes[j]]``
    — one (sizes[j], F) array per device.  The host-side oracle the
    differential tests and the MoE bench's numeric leg compare the SPMD
    executor against.
    """
    p = plan.p
    contribs = [np.asarray(c) for c in contribs]
    F = contribs[0].shape[1]
    dtype = np.result_type(*(c.dtype for c in contribs))
    bufs = np.zeros((p, plan.buf_rows, F), dtype)
    for i in range(p):
        bufs[i, : plan.total] = contribs[i]
    fin = execute_reduce_steps_numpy(plan.steps, bufs)
    return [fin[j, plan.offsets[j]: plan.offsets[j] + plan.sizes[j]]
            for j in range(p)]


def execute_allreducev_plan_numpy(plan, contribs) -> list[np.ndarray]:
    """Run a lowered allreducev plan (reduce_scatterv then allgatherv on
    one buffer) end-to-end in NumPy.  Returns the full (total, F) reduced
    vector, one copy per device — all ``p`` copies must be identical."""
    p = plan.p
    contribs = [np.asarray(c) for c in contribs]
    F = contribs[0].shape[1]
    dtype = np.result_type(*(c.dtype for c in contribs))
    bufs = np.zeros((p, plan.buf_rows, F), dtype)
    for i in range(p):
        bufs[i, : plan.total] = contribs[i]
    bufs = execute_reduce_steps_numpy(plan.rs.steps, bufs)
    # post-reduce state (owner j's reduced block at offsets[j]) is exactly
    # the allgatherv start state; its steps overwrite, never add
    fin = execute_steps_numpy(plan.ag.steps, bufs)
    return [fin[j, : plan.total] for j in range(p)]


def plan_host_times(steps, p: int, params, row_bytes: int = 1,
                    topology=None) -> dict:
    """Per-rank (or per-host) port-occupancy seconds of a lowered plan.

    The span accounting of the NumPy/step-oracle dataplane: each step
    charges both endpoints of every ``(src, dst)`` pair one startup plus
    the bandwidth of the rows actually received (``recv_valid[dst]``
    rows × ``row_bytes``) on their send/recv port, priced through
    :func:`repro.core.costmodel.edge_params_fn` — so a
    ``DegradedCostParams`` overlay (chaos injection, health map) shows
    up in exactly the per-host span times ``StragglerPolicy
    .observe_hosts`` consumes.  Returns ``{rank: seconds}``, or
    ``{host: seconds}`` (max over the host's ranks — its slowest port)
    when a ``HostTopology`` is given.
    """
    from .costmodel import edge_params_fn

    params.validate()
    ab = edge_params_fn(params)
    rb = float(row_bytes)
    t = [0.0] * int(p)
    for perm, payload, send_start, recv_start, recv_valid in steps:
        for s, d in perm:
            a, b = ab(s, d)
            c = a + b * float(recv_valid[d]) * rb
            t[s] += c
            t[d] += c
    if topology is None:
        return {r: t[r] for r in range(int(p))}
    out: dict = {}
    for r in range(int(p)):
        h = topology.host_of(r)
        out[h] = max(out.get(h, 0.0), t[r])
    return out


def execute_scatter_steps_numpy(plan, bufs: np.ndarray) -> np.ndarray:
    """NumPy mirror of ``jax_collectives.scatterv_shard``'s reverse walk:
    the gather plan's steps run backwards with transposed tables (parent
    pushes the same global row ranges back down the tree)."""
    bufs = np.array(bufs, copy=True)
    for perm, payload, send_start, recv_start, recv_valid in \
            reversed(plan.steps):
        snap = bufs.copy()
        for src, dst in perm:
            s0 = int(send_start[src])     # parent reads where child sent
            nv = int(recv_valid[dst])
            bufs[src, s0: s0 + nv] = snap[dst, s0: s0 + nv]
    return bufs
