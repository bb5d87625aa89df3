"""TUW gatherv/scatterv as JAX collectives (shard_map + lax.ppermute).

TPU adaptation of the paper's point-to-point schedules:

* **static-irregular mode** — block sizes are known at trace time (uneven
  parameter shards, per-expert capacities, ragged eval outputs).  The tree
  is built on host; each of the ceil(log2 p) merge rounds becomes ONE
  ``lax.ppermute`` whose permutation is the round's disjoint sender->
  receiver pairs.  Payloads within a round are padded to the round's
  largest transfer (XLA static shapes); rows are addressed with
  device-dependent ``dynamic_slice`` starts so every device runs the same
  SPMD program.  ``bucket_rounds`` splits a round's pairs into size buckets
  (more ppermutes, less padding) — a beyond-paper trade-off measured in
  benchmarks.

* **runtime-ragged mode** — sizes known only at run time (MoE loads).  A
  data-dependent communication graph is not expressible inside one XLA
  program, so sizes quantize to buckets and one compiled executable is
  cached per bucketed size tuple (the standard JAX/TPU raggedness
  answer).  This lives in ``repro.tuner.service.PlannerService``,
  which also *selects* the schedule per calibrated (alpha, beta) and
  covers all six ops.  The fully distributed Lemma-3 construction
  itself IS expressible on device with static scalar ppermutes —
  ``tree_metadata_exchange`` demonstrates it and is property-tested against
  the host construction.

* **composed mode** — irregular collectives built by composing rooted
  trees (``repro.core.composed``).  ``allgatherv`` is the gather schedule
  followed by a broadcast of the packed buffer down the reversed tree;
  ``alltoallv`` is p rooted scatter trees packed round-robin into global
  rounds that are partial permutations.  Both lower exactly like the
  static-irregular mode: one ``lax.ppermute`` per global round (or per
  size bucket), payloads padded to the round maximum, rows addressed by
  device-dependent ``dynamic_slice`` starts into a flat row space that
  concatenates the per-tree coordinate spaces.  ``ComposedPlan`` carries
  the tables and is validated at build time.

* **reduction mode** — ``reduce_scatterv`` runs the composed reduction
  schedules (``repro.core.composed.reduce_scatterv_schedule`` and its
  direct / recursive-halving alternatives) through the SAME lowering and
  executor, with one semantic change: ``_apply_steps(..., reduce=True)``
  swaps the receive-side merge for a fused ADD (``slab_step_reduce``),
  so partial sums fold root-ward instead of blocks overwriting.
  ``allreducev`` chains a reduce_scatterv plan with an allgatherv plan
  on one buffer (the post-reduce state IS the allgatherv start state).
  Fold order per row is fixed by the step tables — results are bitwise
  reproducible run-to-run and across pipelining choices.

* **pipelined mode** (``segments > 1`` on any plan_*) — the same
  schedule re-timed by ``repro.core.pipeline``: the flat row space is
  cut into S global chunks and the chunk-j piece of a round-k transfer
  runs at stage k + j, so each ppermute carries a ``~1/S``-sized
  contiguous slab and rounds overlap across chunks in ``R + S - 1``
  stages (the allgatherv broadcast streams chunks instead of repeating
  the full buffer).  Every step still moves only its live slab —
  extracted/merged at dynamic offsets by the selected slab data plane
  (compiled Pallas kernels by default, see ``set_dataplane``) — and
  results are byte-identical to the monolithic path.

* **hierarchical (multi-host) mode** — nothing in the lowering is
  single-host-specific: a two-level schedule
  (``baselines.two_level_tree`` and the ``tree=``/``tree_builder=``
  overrides of the composed schedules) is just another contiguous tree,
  so it flows through the same legalize → bucket → pipeline → ppermute
  path.  On a mesh with an explicit ``(host, device)`` axis split the
  executors take the axis TUPLE as ``axis_name`` (``("host",
  "device")`` — ``lax.axis_index``/``lax.ppermute`` flatten it
  host-major, exactly the rank layout
  ``costmodel.HostTopology`` assumes), which works unchanged under real
  ``jax.distributed`` multi-process meshes — the conformance lane in
  ``tests/multidevice/child_multihost.py`` runs all four collectives on
  an emulated 2-host x 4-device CPU mesh and asserts byte-identity
  against the single-host oracle.

* **host-staged execution** — :func:`run_plan` runs any plan from host
  arrays: :func:`stage` lays them out as the shard body's input,
  :func:`shard_program` is the jitted ``shard_map`` of the op's body
  (``SHARD_BODIES``), the call goes through :func:`call_with_deadline`,
  and :func:`unstage` reads the result back.  ``PlannerService`` runs its
  six ops through the same program, layout and deadline, at quantized
  strides.

The ordering invariant of the paper carries over: every payload is a
consecutive rank range written at its global offset, so the root's buffer
ends up in rank order with no reordering pass (zero-copy receives).
Composed schedules keep the same invariant in the flat space — a block's
offset is identical on every device that ever holds it, so allgatherv's
result and alltoallv's received blocks land at their consecutive-rank-
range offsets with no reordering.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.metrics import REGISTRY as _OBS_REGISTRY

from .composed import (ComposedSchedule, allgatherv_schedule,
                       alltoallv_schedule, reduce_scatterv_schedule)
from .pipeline import num_stages as _pipeline_num_stages
from .pipeline import pipeline_rounds, pipeline_rounds_per_tree
from .treegather import GatherTree, build_gather_tree, ceil_log2

# --------------------------------------------------------------------------
# slab data plane: Pallas kernels (repro.kernels.ragged_gather) or XLA
# --------------------------------------------------------------------------

DATAPLANES = ("pallas", "interpret", "xla")
_DATAPLANE = "pallas"


def set_dataplane(kind: str) -> None:
    """Select the slab data plane of every SPMD executor.

    ``"pallas"`` (the default) runs the compiled Pallas kernels of
    ``repro.kernels.ragged_gather``, the TPU path.  ``"interpret"`` runs
    the same kernels in Pallas interpret mode, and ``"xla"`` the jnp
    ``dynamic_slice`` reference; the two are for CPU runs.  Nothing
    chooses from the device: a CPU process that keeps the default fails
    when it lowers a kernel instead of silently interpreting it.
    """
    global _DATAPLANE
    if kind not in DATAPLANES:
        raise ValueError(f"unknown data plane {kind!r}; one of {DATAPLANES}")
    _DATAPLANE = kind


def dataplane() -> str:
    """The slab data plane the executors lower with (``set_dataplane``)."""
    return _DATAPLANE


def lane_width(F: int, dtype) -> int:
    """The width at which the executors' capacity buffer holds (N, F) rows
    of ``dtype``: ``ops.lane_width`` on the kernels' row view, padded
    with zero lanes where F is not one the TPU lays out for a DMA at any
    row; F itself on the ``"xla"`` data plane, whose view is the
    identity."""
    if _DATAPLANE == "xla":
        return F
    from repro.kernels.ragged_gather import ops
    return ops.lane_width(F, dtype)


def moved_row_bytes(row_bytes: int, dtype) -> int:
    """The bytes per row the executors' ppermutes move for rows of
    ``row_bytes`` bytes of ``dtype``: the row at :func:`lane_width`.
    ``row_bytes`` that is not whole elements (a row-count unit) is
    returned as it is."""
    itemsize = jnp.dtype(dtype).itemsize
    if row_bytes % itemsize:
        return row_bytes
    return lane_width(row_bytes // itemsize, dtype) * itemsize


# --------------------------------------------------------------------------
# plan construction (host, trace time)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class GathervPlan:
    """Static schedule tables for the SPMD executor.

    All tables are (rounds, p) int32; ``perms`` is a list of ppermute
    permutations per round (possibly several per round when bucketed).
    """

    p: int
    root: int
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]       # global row offset of each block
    total: int                     # sum(sizes)
    cap: int                       # max(sizes): per-device input padding
    buf_rows: int                  # total + spill padding
    # one entry per ppermute call: (perm, payload_rows, send_start, recv_start,
    # recv_valid) -- the *_start/_valid tables are (p,) int32
    steps: tuple[tuple, ...]
    tree_bytes_exact: int          # sum of true transfer sizes (paper cost)
    tree_bytes_padded: int         # what the padded ppermutes actually move
    segments: int = 1              # pipeline segment count S (1 = monolithic)
    stage_ids: tuple[int, ...] = ()  # pipeline stage of each step (len(steps))
    num_stages: int = 0            # R + S - 1 stages (R for S = 1)
    wave_bin_ratio: float = 0.0    # payload-bin ratio (0 = fixed-count split)

    @property
    def padding_overhead(self) -> float:
        """Relative padding cost of the slab data plane, as a fraction.

        Every ppermute step carries one contiguous slab per pair, padded
        to the LARGEST slab in its step group (XLA static shapes) — never
        the whole capacity buffer.  ``tree_bytes_padded`` sums those
        per-step payloads over all pairs; ``tree_bytes_exact`` sums the
        true slab sizes (the paper's linear cost).  The ratio minus one is
        therefore the within-step padding waste only: 0.0 means every
        slab in every step group was the same size.  ``bucket_rounds`` and
        pipeline ``segments`` both shrink it by making step groups more
        homogeneous.
        """
        if self.tree_bytes_exact == 0:
            return 0.0
        return self.tree_bytes_padded / self.tree_bytes_exact - 1.0


def _legalize_round(transfers):
    """Split one round's transfers into ppermute-legal waves.

    A ``lax.ppermute`` permutation needs unique sources AND unique
    destinations.  TUW merge rounds and composed global rounds satisfy that
    by construction, but baseline trees the tuner may select do not (a
    linear tree funnels every sender into the root in round 0) — those
    serialize on the shared endpoint's port in the telephone model, which
    is exactly what consecutive waves express.  Greedy first-fit preserves
    the (size-sorted) order within a wave.
    """
    waves: list[tuple[set, set, list]] = []
    for t in transfers:
        src, dst = t[0], t[1]
        for srcs, dsts, group in waves:
            if src not in srcs and dst not in dsts:
                srcs.add(src)
                dsts.add(dst)
                group.append(t)
                break
        else:
            waves.append(({src}, {dst}, [t]))
    return [group for _, _, group in waves]


def _wave_groups(wave, bucket_rounds: int, wave_bin_ratio: float):
    """Split one legalized wave's (size-sorted) transfers into step groups.

    Two policies:

    * ``wave_bin_ratio > 1`` — PAYLOAD-BINNED packing: walk the sorted
      transfers and open a new group whenever a size exceeds
      ``wave_bin_ratio`` times the current group's smallest member, i.e.
      geometric size bins.  Every group's padded bytes are then at most
      ``wave_bin_ratio`` times its exact bytes, so within-step padding is
      BOUNDED on arbitrarily skewed size mixes — the fixed-count split
      below has no such bound (one huge and many tiny transfers in the
      same bucket still pad everything to the maximum).  Homogeneous
      waves stay a single group, so uniform matrices pay nothing.
    * otherwise — the legacy fixed-count split into up to
      ``bucket_rounds`` equal-count buckets.
    """
    if wave_bin_ratio and wave_bin_ratio > 1.0:
        groups: list[list] = []
        cur: list = []
        cur_min = 1
        for t in wave:
            if cur and t[2] > cur_min * wave_bin_ratio:
                groups.append(cur)
                cur = []
            if not cur:
                cur_min = max(1, t[2])
            cur.append(t)
        if cur:
            groups.append(cur)
        return groups
    nb = min(bucket_rounds, len(wave))
    return [[wave[i] for i in idx]
            for idx in np.array_split(np.arange(len(wave)), nb)
            if len(idx)]


def _bucketed_steps(rounds, p: int, bucket_rounds: int,
                    wave_bin_ratio: float = 0.0):
    """Lower transfer rounds to ppermute step tables.

    ``rounds``: list of rounds (or pipeline stages), each a list of
    ``(src, dst, size, start)``.  Rounds with endpoint conflicts are first
    split into permutation-legal waves (see ``_legalize_round``); each
    wave then becomes ppermute steps per :func:`_wave_groups` — up to
    ``bucket_rounds`` equal-count size buckets, or geometric payload bins
    when ``wave_bin_ratio > 1`` (extra latency, bounded padding).
    Returns ``(steps, exact, padded, max_payload, stage_ids)`` where
    ``stage_ids[k]`` is the index of the round/stage step ``k`` lowered
    from — the pipeline cost model groups steps by it.  The two split
    policies are mutually exclusive: asking for both is a conflict, not
    a composition, and raises.
    """
    if wave_bin_ratio and wave_bin_ratio > 1.0 and bucket_rounds > 1:
        raise ValueError(
            "bucket_rounds > 1 and wave_bin_ratio > 1 are alternative "
            "wave-split policies; pass one or the other")
    steps = []
    stage_ids = []
    exact = 0
    padded = 0
    max_payload = 1
    for stage, rnd in enumerate(rounds):
        transfers = sorted(rnd, key=lambda t: t[2])
        if not transfers:
            continue
        for wave in _legalize_round(transfers):
            for group in _wave_groups(wave, bucket_rounds, wave_bin_ratio):
                payload = max(t[2] for t in group)
                send_start = np.zeros(p, np.int32)
                recv_start = np.zeros(p, np.int32)
                recv_valid = np.zeros(p, np.int32)
                perm = []
                for src, dst, size, start in group:
                    perm.append((src, dst))
                    send_start[src] = start
                    recv_start[dst] = start
                    recv_valid[dst] = size
                    exact += size
                    padded += payload
                steps.append((tuple(perm), int(payload), send_start,
                              recv_start, recv_valid))
                stage_ids.append(stage)
                max_payload = max(max_payload, payload)
    return tuple(steps), exact, padded, max_payload, tuple(stage_ids)


def plan_gatherv(sizes, root: int, tree: GatherTree | None = None,
                 bucket_rounds: int = 1, segments: int = 1,
                 wave_bin_ratio: float = 0.0) -> GathervPlan:
    """Build the SPMD schedule for a gatherv over ``p = len(sizes)`` devices.

    ``bucket_rounds > 1`` splits each merge round's pairs into up to that
    many size buckets, each its own ppermute: extra latency, less padding.
    ``wave_bin_ratio > 1`` uses geometric payload bins instead (see
    ``_wave_groups``): padded bytes stay within that factor of exact bytes
    on arbitrarily skewed rounds.
    ``segments > 1`` pipelines the schedule (``repro.core.pipeline``): the
    flat row space is cut into that many global chunks and the chunk-``j``
    piece of a round-``k`` transfer runs at stage ``k + j``, so each
    ppermute carries ``~1/segments`` of the payload and rounds overlap
    across segments in ``rounds + segments - 1`` stages.
    """
    sizes = tuple(int(s) for s in sizes)
    p = len(sizes)
    if tree is None:
        tree = build_gather_tree(list(sizes), root=root)
    assert tree.root == root and tree.p == p
    for e in tree.edges:
        if e.size > 0 and e.lo < 0:
            raise ValueError(
                f"tree {tree.name!r} has a non-contiguous transfer "
                "(lo=-1): the zero-copy data plane needs consecutive "
                "block-rank ranges")
    offsets = tuple(int(x) for x in np.concatenate([[0], np.cumsum(sizes)[:-1]]))
    total = int(sum(sizes))
    cap = max(1, max(sizes))

    by_round: dict[int, list] = {}
    for e in tree.edges:
        if e.size == 0:
            continue  # paper: no actual communication for empty blocks
        by_round.setdefault(e.round, []).append(e)
    rounds = [
        [(e.child, e.parent, e.size, offsets[e.lo]) for e in by_round[rnd]]
        for rnd in sorted(by_round)
    ]
    n_rounds = len(rounds)
    rounds = pipeline_rounds(rounds, segments, total)
    steps, exact, padded, max_payload, stage_ids = _bucketed_steps(
        rounds, p, bucket_rounds, wave_bin_ratio)
    buf_rows = total + max(cap, max_payload)
    return GathervPlan(p, root, sizes, offsets, total, cap, buf_rows,
                       steps, exact, padded, segments=int(segments),
                       stage_ids=stage_ids,
                       num_stages=_pipeline_num_stages(n_rounds, segments),
                       wave_bin_ratio=float(wave_bin_ratio))


# --------------------------------------------------------------------------
# SPMD executors (call inside shard_map)
# --------------------------------------------------------------------------

def _slab_ops(reduce: bool = False):
    """``(extract, merge, step, view)`` of the selected data plane
    (``set_dataplane``): the Pallas kernels, compiled or interpreted, or
    the jnp oracles of ``repro.kernels.ragged_gather.ref`` — one
    definition of the slab semantics per backend.  ``step`` is the FUSED
    merge-then-extract op the executors run between consecutive
    ppermutes; ``view`` maps the (N, F) buffer to the layout the ops
    take (the kernels' DMA row view).  ``reduce=True`` swaps in the
    fused-ADD variants (``slab_merge_add`` / ``slab_step_reduce``):
    received slabs fold into the accumulator instead of overwriting it —
    the only semantic difference between the byte-moving and the
    reducing data planes.  The oracles run under ``jax.named_scope`` of
    the kernel they stand for (``KERNEL_NAMES``), so an op's
    ``op_name`` is the same on either data plane."""
    if _DATAPLANE == "xla":
        from repro.kernels.ragged_gather import ref
        if reduce:
            return (_scoped("slab_extract", ref.slab_extract_ref),
                    _scoped("slab_merge_add", ref.slab_merge_add_ref),
                    _scoped("slab_step_reduce", ref.slab_step_reduce_ref),
                    lambda buf: buf)
        return (_scoped("slab_extract", ref.slab_extract_ref),
                _scoped("slab_merge", ref.slab_merge_ref),
                _scoped("slab_step", ref.slab_step_ref), lambda buf: buf)
    from repro.kernels.ragged_gather import ops
    kw = {"interpret": _DATAPLANE == "interpret"}
    merge, step = ((ops.slab_merge_add, ops.slab_step_reduce) if reduce
                   else (ops.slab_merge, ops.slab_step))
    return (functools.partial(ops.slab_extract, **kw),
            functools.partial(merge, **kw), functools.partial(step, **kw),
            ops.row_view)


def _slab_unpack():
    """The ``slab_unpack`` op of the selected data plane (``set_dataplane``),
    which builds alltoallv's output from its blocks in the buffer: the
    Pallas kernel, compiled or interpreted, whose rows outside the blocks
    are uninitialised, or the jnp oracle, whose rows there are zero, under
    the kernel's name."""
    if _DATAPLANE == "xla":
        from repro.kernels.ragged_gather import ref
        return _scoped("slab_unpack", ref.slab_unpack_ref)
    from repro.kernels.ragged_gather import ops
    return functools.partial(ops.slab_unpack,
                             interpret=_DATAPLANE == "interpret")


def _scoped(name: str, fn):
    """``fn`` run under ``jax.named_scope(name)``."""
    def run(*args):
        with jax.named_scope(name):
            return fn(*args)
    return run


# Device scopes of the executors' phases (``jax.named_scope``).  A scope
# lands in the ``op_name`` metadata of every op traced inside it, which a
# profiler shows as the op's ``tf_op``; it changes no op of the program.
FILL = "ragged.fill"                  # capacity buffer: own input (+ zeros)
RELAYOUT_IN = "ragged.relayout_in"    # ``row_view`` of the executor's input
STEP = "ragged.step"                  # each step's slab op
PPERMUTE = "ragged.ppermute"          # each step's ``lax.ppermute``
RELAYOUT_OUT = "ragged.relayout_out"  # whole buffer returned as (N, F)
UNPACK = "ragged.unpack"              # the output taken from the buffer
LANE_PAD = "ragged.lane_pad"          # rows padded to / sliced from lane_width
SCOPES = (FILL, RELAYOUT_IN, STEP, PPERMUTE, RELAYOUT_OUT, UNPACK, LANE_PAD)


def _pad_lanes(x: jax.Array) -> jax.Array:
    """(N, F) rows padded with zero lanes to :func:`lane_width`; ``x``
    itself, with no op, where F is that width already."""
    F = x.shape[1]
    pad = lane_width(F, x.dtype) - F
    if not pad:
        return x
    with jax.named_scope(LANE_PAD):
        return jnp.pad(x, ((0, 0), (0, pad)))


def _unpad_lanes(x: jax.Array, F: int) -> jax.Array:
    """The first ``F`` lanes of (N, W) rows; ``x`` itself where W is F."""
    if x.shape[1] == F:
        return x
    with jax.named_scope(LANE_PAD):
        return x[:, :F]


def _fill(x_local: jax.Array, buf_rows: int, start, *,
          zero: bool = True) -> jax.Array:
    """The capacity buffer in the data plane's row view (``_slab_ops``'
    ``view``) of rows at :func:`lane_width`: ``buf_rows`` rows with
    ``x_local``'s rows written at row ``start``.  Only the input is
    padded and relaid; the buffer is built in the view and stays in it
    through the steps to the unpack.

    ``zero=True`` zeroes every other row.  ``zero=False`` leaves them
    uninitialised on the Pallas data planes (the ``slab_fill`` kernel
    writes the input alone; the ``"xla"`` oracle still zeroes), which is
    right only for an executor that reads no row outside its input and
    the valid prefixes it has received but under a mask: alltoallv,
    whose steps merge only ``recv_valid`` prefixes and whose unpack
    (``slab_unpack``) copies only each block's valid rows.  gatherv and
    allgatherv return the whole buffer, and reduce_scatterv and
    allreducev fold received slabs into it, so those keep the zeros."""
    view = _slab_ops()[3]
    x = _pad_lanes(x_local)
    with jax.named_scope(RELAYOUT_IN):
        x = view(x)
    with jax.named_scope(FILL):
        if not zero and _DATAPLANE != "xla":
            from repro.kernels.ragged_gather import ops
            return ops.slab_fill(x, buf_rows, start,
                                 interpret=_DATAPLANE == "interpret")
        buf = jnp.zeros((buf_rows,) + x.shape[1:], x.dtype)
        # spill rows past the input are later overwritten by received
        # ranges (module docstring invariant)
        return jax.lax.dynamic_update_slice(
            buf, x, (start,) + (jnp.int32(0),) * (x.ndim - 1))


def _rows(buf: jax.Array, start, n: int, F: int) -> jax.Array:
    """``n`` rows of the viewed buffer from row ``start``, as (n, F): only
    the rows taken are relaid and cut to their F lanes."""
    at = (start,) + (jnp.int32(0),) * (buf.ndim - 1)
    rows = jax.lax.dynamic_slice(buf, at, (n,) + buf.shape[1:])
    return _unpad_lanes(rows.reshape(n, -1), F)


def _flat(buf: jax.Array, F: int) -> jax.Array:
    """The whole viewed buffer as (N, F): the output's own relayout."""
    with jax.named_scope(RELAYOUT_OUT):
        out = buf.reshape(buf.shape[0], -1)
    return _unpad_lanes(out, F)


def _apply_steps(buf: jax.Array, steps, r, axis_name: str,
                 reduce: bool = False) -> jax.Array:
    """Run ppermute step tables over a flat row buffer (shared by the
    gatherv, scatterv, and composed executors).  The buffer comes in the
    data plane's row view (``_slab_ops``' ``view``; ``_fill`` builds it
    there) and leaves in it, never relaid here.  Each step: extract the
    ``payload``-row slab at the device's send offset, permute ONLY that
    slab (never the whole capacity buffer), merge the valid prefix at the
    device's receive offset (same flat offset: zero-copy invariant).

    Between consecutive ppermutes, the step-``k`` merge and the
    step-``k+1`` extract are FUSED into one op (``step``): it folds the
    received slab in and reads the next outgoing slab from the merged
    state — the extract MUST see the merge result, because a forwarded
    slab may contain rows that just arrived.  So a plan runs as a
    leading extract, one fused local op per ppermute, and a trailing
    merge, all through the selected data plane (``set_dataplane``).

    ``reduce=True`` runs the same loop with the fused-ADD ops: each
    received slab is summed into the receiver's rows.  ppermute hands
    non-recipients a zero slab, but their ``recv_valid`` table entry is
    0, so the masked add leaves their accumulator bit-exact.
    """
    if not steps:
        return buf
    extract, merge, step, _ = _slab_ops(reduce)
    _, payload0, send0, _, _ = steps[0]
    with jax.named_scope(STEP):
        out = extract(buf, jnp.asarray(send0)[r], payload0)
    for k, (perm, payload, send_start, recv_start, recv_valid) in \
            enumerate(steps):
        with jax.named_scope(PPERMUTE):
            got = jax.lax.ppermute(out, axis_name, perm)
        with jax.named_scope(STEP):
            r0 = jnp.asarray(recv_start)[r]
            nv = jnp.asarray(recv_valid)[r]
            if k + 1 < len(steps):
                _, npayload, nsend, _, _ = steps[k + 1]
                buf, out = step(buf, got, r0, nv, jnp.asarray(nsend)[r],
                                npayload)
            else:
                buf = merge(buf, got, r0, nv)
    return buf


def gatherv_shard(x_local: jax.Array, plan: GathervPlan, axis_name: str) -> jax.Array:
    """Per-shard gatherv body.  ``x_local``: (cap, F) padded local block.
    Returns (buf_rows, F); rows [0:total] at the root hold all blocks in
    rank order.  Call under shard_map with in/out specs P(axis_name).
    """
    r = jax.lax.axis_index(axis_name)
    offs = jnp.asarray(plan.offsets, jnp.int32)
    # own (padded) block at its global offset
    buf = _fill(x_local, plan.buf_rows, offs[r])
    return _flat(_apply_steps(buf, plan.steps, r, axis_name),
                 x_local.shape[1])


def _reversed_step_tables(plan: "GathervPlan") -> tuple[tuple, ...]:
    """Scatter step tables: the gather steps reversed with transposed
    permutations.  Reversed edge parent -> child, same global row range:
    in the gather step the child sent rows [send_start[child], +size); in
    scatter the parent sends those rows back down.  Host-side table
    transposition (trace time, cheap); the result has the exact step-table
    format ``_apply_steps`` consumes, so the fused-kernel executor covers
    scatter too."""
    out = []
    for perm, payload, send_start, recv_start, recv_valid in \
            reversed(plan.steps):
        rperm = tuple((dst, src) for (src, dst) in perm)
        p_send = np.zeros(plan.p, np.int32)   # parent's read offset
        c_recv = np.zeros(plan.p, np.int32)   # child's write offset
        c_valid = np.zeros(plan.p, np.int32)  # child's valid rows
        for (src, dst) in perm:
            p_send[dst] = send_start[src]
            c_recv[src] = send_start[src]
            c_valid[src] = recv_valid[dst]
        out.append((rperm, payload, p_send, c_recv, c_valid))
    return tuple(out)


def scatterv_shard(buf_root: jax.Array, plan: GathervPlan, axis_name: str) -> jax.Array:
    """Per-shard scatterv body (reverse schedule).

    ``buf_root``: (buf_rows, F); only the root's rows [0:total] are read.
    Returns the local (cap, F) block for every device.
    """
    r = jax.lax.axis_index(axis_name)
    offs = jnp.asarray(plan.offsets, jnp.int32)
    buf = _pad_lanes(buf_root)
    with jax.named_scope(RELAYOUT_IN):   # the input is the whole buffer
        buf = _slab_ops()[3](buf)
    buf = _apply_steps(buf, _reversed_step_tables(plan), r, axis_name)
    with jax.named_scope(UNPACK):
        return _rows(buf, offs[r], plan.cap, buf_root.shape[1])


# --------------------------------------------------------------------------
# step deadline and fault hook of host-staged executions
# --------------------------------------------------------------------------

class InjectedFault(RuntimeError):
    """A chaos-injected delivery failure (one attempt); retried like a
    real transient fault."""


class CollectiveTimeout(RuntimeError):
    """A collective missed its per-step deadline after bounded retry.

    Raised instead of hanging so the caller (``runtime.restart.TrainLoop``)
    can escalate to the straggler policy — warn → backup → evict."""

    def __init__(self, op: str, attempts: int, deadline_s: float,
                 last_s: float):
        super().__init__(
            f"collective {op!r} missed its {deadline_s * 1e3:.1f} ms step "
            f"deadline after {attempts} attempt(s) "
            f"(last took {last_s * 1e3:.1f} ms)")
        self.op = op
        self.attempts = attempts
        self.deadline_s = deadline_s
        self.last_s = last_s


# step-deadline config for every host-staged execution; None disables it.
_STEP_DEADLINE = {"deadline_s": None, "retries": 2, "backoff": 2.0,
                  "sleep_s": 0.0}
_FAULT_HOOK = None  # callable(op, attempt) raising InjectedFault, or None


def configure_step_deadline(deadline_s: float | None, retries: int = 2,
                            backoff: float = 2.0,
                            sleep_s: float = 0.0) -> None:
    """Arm (or disarm, ``deadline_s=None``) the per-step deadline.

    Every host-staged execution (:func:`run_plan`, ``PlannerService``'s
    ops) gets ``retries`` retries; attempt ``k``
    is allowed ``deadline_s * backoff**k`` (bounded exponential backoff —
    transient congestion gets more slack each try), with an optional
    ``sleep_s``-seeded backoff sleep between attempts.  The final miss
    raises :class:`CollectiveTimeout`.
    """
    _STEP_DEADLINE.update(deadline_s=(None if deadline_s is None
                                      else float(deadline_s)),
                          retries=int(retries), backoff=float(backoff),
                          sleep_s=float(sleep_s))


def set_fault_hook(hook) -> None:
    """Install a chaos hook called as ``hook(op, attempt)`` before every
    host-staged execution attempt; raising :class:`InjectedFault` fails
    that attempt into the retry path.  ``None`` uninstalls."""
    global _FAULT_HOOK
    _FAULT_HOOK = hook


_MISSED = object()


def call_with_deadline(op: str, thunk):
    """Run ``thunk`` under the step deadline + bounded retry.

    Returns ``(result, seconds, attempts)``.  An attempt fails if the
    fault hook injects a fault or the wall time exceeds this attempt's
    allowance; after ``retries`` failed retries, raises
    :class:`CollectiveTimeout` instead of hanging the step.
    """
    deadline = _STEP_DEADLINE["deadline_s"]
    retries = int(_STEP_DEADLINE["retries"])
    backoff = float(_STEP_DEADLINE["backoff"])
    sleep_s = float(_STEP_DEADLINE["sleep_s"])
    attempt = 0
    while True:
        t0 = time.perf_counter()
        try:
            if _FAULT_HOOK is not None:
                _FAULT_HOOK(op, attempt)
            out = thunk()
        except InjectedFault:
            out = _MISSED
        dt = time.perf_counter() - t0
        allowance = (None if deadline is None
                     else deadline * backoff ** attempt)
        if out is not _MISSED and (allowance is None or dt <= allowance):
            return out, dt, attempt + 1
        attempt += 1
        if attempt > retries:
            raise CollectiveTimeout(op, attempt, deadline or 0.0, dt)
        _OBS_REGISTRY.counter("run_retries").inc()
        if sleep_s:
            time.sleep(min(sleep_s * backoff ** (attempt - 1), 1.0))


# --------------------------------------------------------------------------
# composed collectives: allgatherv / alltoallv (repro.core.composed)
# --------------------------------------------------------------------------

def _validate_steps(steps, buf_rows: int, exact: int, padded: int) -> None:
    """ppermute legality and bounds of a step table over a ``buf_rows``
    buffer whose valid rows sum to ``exact`` and payloads to ``padded``;
    raises AssertionError on violation."""
    recv_total = 0
    for perm, payload, send_start, recv_start, recv_valid in steps:
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs), "step has a double sender"
        assert len(set(dsts)) == len(dsts), "step has a double receiver"
        assert 1 <= payload
        for s, d in perm:
            assert 0 <= send_start[s] <= buf_rows - payload
            assert 0 <= recv_start[d] <= buf_rows - payload
            assert 0 < recv_valid[d] <= payload
            recv_total += int(recv_valid[d])
    assert recv_total == exact
    assert exact <= padded


@dataclass(frozen=True)
class ComposedPlan:
    """Validated SPMD schedule for a composed collective.

    Same step-table format as :class:`GathervPlan` (so the same
    ``_apply_steps`` executor runs it), plus the flat-row-space layout:
    device ``i`` writes its input at ``in_starts[i]``; for alltoallv the
    ``extract`` tables copy the valid rows of each received block from
    its flat offset to its consecutive-rank-range output offset (the
    ``slab_unpack`` op; ``chunk`` bounds every block's rows).
    """

    kind: str                       # "allgatherv" | "alltoallv"
    p: int
    root: int                       # allgatherv gather root; -1 alltoallv
    total: int                      # flat row-space rows
    cap: int                        # per-device input rows (padded)
    buf_rows: int                   # working buffer rows (total + spill)
    in_starts: tuple[int, ...]      # where device i's input lives (flat)
    out_valid: tuple[int, ...]      # true output rows per device
    out_rows: int                   # output rows (incl. spill), 8-row tiles
    steps: tuple[tuple, ...]        # (perm, payload, send/recv tables)
    extract: tuple[tuple, ...]      # alltoallv: (src_start, dst_start, valid)
    chunk: int                      # most rows of one extracted block
    num_rounds: int                 # composed global rounds (pre-bucketing)
    tree_bytes_exact: int
    tree_bytes_padded: int
    segments: int = 1               # pipeline segment count S (1 = monolithic)
    stage_ids: tuple[int, ...] = ()   # pipeline stage of each step
    num_stages: int = 0             # rounds + S - 1 stages
    wave_bin_ratio: float = 0.0     # payload-bin ratio (0 = fixed-count)

    @property
    def padding_overhead(self) -> float:
        """Relative padding cost of the slab data plane, as a fraction.

        Same contract as :meth:`GathervPlan.padding_overhead`: each
        ppermute step moves one contiguous slab per pair, padded to the
        largest slab in its step group — not the whole capacity buffer —
        so this ratio measures within-step size spread only.  For
        allgatherv the broadcast-phase slabs are all ``total`` rows (or
        ``total/S`` pipelined), so its overhead comes from the gather
        phase; for alltoallv it reflects how unevenly the packed scatter
        trees' slabs bucket together.
        """
        if self.tree_bytes_exact == 0:
            return 0.0
        return self.tree_bytes_padded / self.tree_bytes_exact - 1.0

    def validate(self) -> None:
        """ppermute legality + bounds; raises AssertionError on violation."""
        _validate_steps(self.steps, self.buf_rows, self.tree_bytes_exact,
                        self.tree_bytes_padded)
        for src_start, dst_start, valid in self.extract:
            for i in range(self.p):
                if valid[i] > 0:
                    assert 0 <= src_start[i] <= self.buf_rows - self.chunk
                    assert 0 <= dst_start[i] <= self.out_rows - self.chunk
                    assert valid[i] <= self.chunk


def plan_allgatherv(sizes, root: int | None = None,
                    bucket_rounds: int = 1, segments: int = 1,
                    wave_bin_ratio: float = 0.0, validate: bool = True,
                    schedule: ComposedSchedule | None = None) -> ComposedPlan:
    """Lower an allgatherv schedule (gather + broadcast) to ppermute steps.

    Every device ends with all blocks in rank order in rows [0:total] of
    its buffer.  ``root=None`` lets the algorithm choose the gather root
    (Lemma 1, no waiting penalty).  ``segments > 1`` pipelines the whole
    composed schedule — gather and broadcast phases stream the same global
    row chunks, so broadcast stage ``j`` starts as soon as chunk ``j`` is
    complete at the root instead of waiting for the full gather.
    ``wave_bin_ratio > 1`` packs each wave into geometric payload bins
    (bounded within-step padding).  ``validate=False`` skips the
    O(steps·p) structural check — the PlanCache hot path disables it
    because every schedule shape it lowers is already covered by the
    validating tests; direct callers keep it on.

    Pipelined plans default to the CHAIN broadcast (every port sends the
    buffer once, so chunking genuinely collapses the broadcast β term);
    monolithic plans keep the reversed-tree broadcast (fewest startups).
    Pass ``schedule`` explicitly to override.
    """
    if schedule is None:
        schedule = allgatherv_schedule(
            sizes, root=root, broadcast="chain" if segments > 1 else "tree")
    assert schedule.kind == "allgatherv"
    # a prebuilt schedule must describe THIS problem, not a stale one
    assert (schedule.sizes[0] == np.asarray([int(s) for s in sizes])).all(), \
        "schedule was built for different block sizes"
    assert root is None or schedule.root == root, \
        "schedule was built for a different root"
    sizes = tuple(int(s) for s in schedule.sizes[0])
    p = schedule.p
    total = schedule.total_rows
    cap = max(1, max(sizes, default=0))
    offsets = tuple(int(x) for x in schedule.offsets(0))
    rounds = [[(t.src, t.dst, t.size, t.start) for t in rnd]
              for rnd in schedule.rounds]
    rounds = pipeline_rounds(rounds, segments, total)
    steps, exact, padded, max_payload, stage_ids = _bucketed_steps(
        rounds, p, bucket_rounds, wave_bin_ratio)
    buf_rows = total + max(cap, max_payload)
    plan = ComposedPlan(
        "allgatherv", p, schedule.root, total, cap, buf_rows,
        in_starts=offsets, out_valid=(total,) * p, out_rows=buf_rows,
        steps=steps, extract=(), chunk=1, num_rounds=schedule.num_rounds,
        tree_bytes_exact=exact, tree_bytes_padded=padded,
        segments=int(segments), stage_ids=stage_ids,
        num_stages=_pipeline_num_stages(schedule.num_rounds, segments),
        wave_bin_ratio=float(wave_bin_ratio))
    if validate:
        plan.validate()
    return plan


def plan_alltoallv(size_matrix, bucket_rounds: int = 1, segments: int = 1,
                   wave_bin_ratio: float = 0.0, validate: bool = True,
                   schedule: ComposedSchedule | None = None) -> ComposedPlan:
    """Lower an alltoallv schedule (p packed scatter trees, or the direct
    pairwise rounds of ``alltoallv_direct_schedule``) to ppermute steps
    plus per-tree extraction tables.

    Device ``i`` supplies its packed row (blocks destined to ranks
    0..p-1, concatenated); it receives blocks from all sources, each at
    its consecutive-rank-range output offset ``sum_{i'<i} S[i'][j]``.

    ``segments > 1`` pipelines the schedule PER TREE
    (``repro.core.pipeline.pipeline_rounds_per_tree``): every source
    tree's own row span is cut into ``segments`` chunks, so every
    transfer genuinely shrinks to ``~1/segments`` slabs and same-stage
    pieces of different trees fuse into shared ppermute waves (one α per
    wave).  Global chunking of the concatenated row space — what
    ``plan_gatherv``/``plan_allgatherv`` do, and what this op did before
    — leaves whole trees inside single chunks, delaying them without
    splitting anything.  ``wave_bin_ratio > 1`` packs each wave into
    geometric payload bins (bounded within-step padding on skewed MoE
    matrices).  ``validate=False`` skips the O(steps·p) structural check
    (PlanCache hot path).
    """
    if schedule is None:
        schedule = alltoallv_schedule(size_matrix)
    assert schedule.kind == "alltoallv"
    # a prebuilt schedule must describe THIS problem, not a stale one
    assert (schedule.sizes == np.asarray(size_matrix, dtype=np.int64)).all(), \
        "schedule was built for a different size matrix"
    S = schedule.sizes
    p = schedule.p
    row_totals = S.sum(axis=1)
    col_totals = S.sum(axis=0)
    total = schedule.total_rows
    cap = max(1, int(row_totals.max(initial=0)))
    chunk = max(1, int(S.max(initial=0)))
    rounds = [[(t.src, t.dst, t.size, t.start) for t in rnd]
              for rnd in schedule.rounds]
    # per-tree segmentation: each source tree's own row span is chunked
    # independently (zero-row trees contribute no transfers and no spans)
    tree_spans = [(int(schedule.row_starts[r]),
                   int(schedule.row_starts[r]) + int(row_totals[r]))
                  for r in range(p) if row_totals[r] > 0]
    rounds = pipeline_rounds_per_tree(rounds, segments, tree_spans)
    steps, exact, padded, max_payload, stage_ids = _bucketed_steps(
        rounds, p, bucket_rounds, wave_bin_ratio)
    buf_rows = total + max(cap, max_payload, chunk)
    out_valid = tuple(int(c) for c in col_totals)
    # whole 8-row tiles: XLA then fuses the output's relayout out of the
    # kernels' row view with alltoallv_shard's row mask into one op
    out_rows = -(-(max(1, int(col_totals.max(initial=0))) + chunk) // 8) * 8
    # output offsets: block (r -> j) lands at sum_{i<r} S[i][j] — the
    # column-wise consecutive-rank-range invariant
    dst_off = np.concatenate([np.zeros((1, p), np.int64),
                              np.cumsum(S, axis=0)[:-1]])
    extract = []
    for r in range(p):
        if row_totals[r] == 0:
            continue
        offs = schedule.offsets(r)
        src_start = (int(schedule.row_starts[r]) + offs).astype(np.int32)
        dst_start = dst_off[r].astype(np.int32)
        valid = S[r].astype(np.int32)
        extract.append((src_start, dst_start, valid))
    plan = ComposedPlan(
        "alltoallv", p, -1, total, cap, buf_rows,
        in_starts=tuple(int(x) for x in schedule.row_starts),
        out_valid=out_valid, out_rows=out_rows, steps=steps,
        extract=tuple(extract), chunk=chunk, num_rounds=schedule.num_rounds,
        tree_bytes_exact=exact, tree_bytes_padded=padded,
        segments=int(segments), stage_ids=stage_ids,
        num_stages=_pipeline_num_stages(schedule.num_rounds, segments),
        wave_bin_ratio=float(wave_bin_ratio))
    if validate:
        plan.validate()
    return plan


def allgatherv_shard(x_local: jax.Array, plan: ComposedPlan,
                     axis_name: str) -> jax.Array:
    """Per-shard allgatherv body.  ``x_local``: (cap, F) padded block.
    Returns (buf_rows, F); rows [0:total] hold all blocks in rank order on
    EVERY device (gather rounds, then broadcast rounds)."""
    r = jax.lax.axis_index(axis_name)
    starts = jnp.asarray(plan.in_starts, jnp.int32)
    buf = _fill(x_local, plan.buf_rows, starts[r])
    return _flat(_apply_steps(buf, plan.steps, r, axis_name),
                 x_local.shape[1])


def alltoallv_shard(x_local: jax.Array, plan: ComposedPlan,
                    axis_name: str) -> jax.Array:
    """Per-shard alltoallv body.  ``x_local``: (cap, F) packed row of
    blocks destined to ranks 0..p-1.  Returns (out_rows, F); rows
    [0:out_valid[j]] on device j are the received blocks ordered by
    source rank (each at its consecutive-rank-range offset), and the
    rows past them are 0."""
    r = jax.lax.axis_index(axis_name)
    starts = jnp.asarray(plan.in_starts, jnp.int32)
    # every read of the buffer outside the input and the received prefixes
    # is masked, so its other rows need no zeros (``_fill``)
    buf = _fill(x_local, plan.buf_rows, starts[r], zero=False)
    buf = _apply_steps(buf, plan.steps, r, axis_name)
    with jax.named_scope(UNPACK):
        # (blocks, 3, p): each source block's src_start, dst_start and
        # valid on every rank
        tables = np.asarray(plan.extract, np.int32).reshape(-1, 3, plan.p)
        src_start, dst_start, valid = jnp.asarray(
            tables.transpose(1, 0, 2))[:, :, r]
        out = _slab_unpack()(buf, src_start, dst_start, valid, plan.chunk,
                             plan.out_rows)
        # the blocks' valid rows tile [0, out_valid[r]); the rows past it
        # read 0, set in the op that relays the output out of the view
        out = out.reshape(plan.out_rows, -1)
        rows = jnp.arange(plan.out_rows, dtype=jnp.int32)[:, None]
        out = jnp.where(rows < jnp.asarray(plan.out_valid, jnp.int32)[r],
                        out, jnp.zeros((), out.dtype))
        return _unpad_lanes(out, x_local.shape[1])


# --------------------------------------------------------------------------
# reduction collectives: reduce_scatterv / allreducev
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ReduceScattervPlan:
    """Validated SPMD schedule for reduce_scatterv.

    Same step-table format as :class:`GathervPlan`/:class:`ComposedPlan`
    — the SAME ``_apply_steps`` executor runs it, with ``reduce=True``
    swapping the merge for the fused ADD (``slab_step_reduce``).  Every
    device supplies a full (total, F) contribution vector in flat layout
    (segment ``j``'s rows at ``offsets[j]``); device ``j`` ends with
    ``sum_i contribution_i[offsets[j]: offsets[j]+sizes[j]]``.

    Bitwise determinism: the step tables are a pure function of
    ``sizes`` (host-built, no timing dependence), each flat row receives
    at most one fold per step (unique receiver per wave + disjoint row
    ranges per round), and every fold is ordered by step index — so the
    floating-point summation order per row is FIXED, making results
    reproducible run-to-run and pipelined plans bit-identical to their
    monolithic counterparts.
    """

    p: int
    sizes: tuple[int, ...]          # rows owned (received) by each rank
    offsets: tuple[int, ...]        # flat row offset of each segment
    total: int                      # sum(sizes)
    cap: int                        # output rows per device (padded)
    in_rows: int                    # input rows per device (>= 1)
    buf_rows: int                   # working buffer rows (total + spill)
    steps: tuple[tuple, ...]        # (perm, payload, send/recv tables)
    num_rounds: int                 # schedule rounds (pre-bucketing)
    tree_bytes_exact: int
    tree_bytes_padded: int
    segments: int = 1               # pipeline segment count S
    stage_ids: tuple[int, ...] = ()   # pipeline stage of each step
    num_stages: int = 0             # rounds + S - 1 stages
    wave_bin_ratio: float = 0.0

    @property
    def padding_overhead(self) -> float:
        """Within-step slab padding as a fraction (0.0 when nothing
        moves — the all-zero / p=1 degenerate shapes must not divide by
        zero; same guarded contract as the byte-moving plans)."""
        if self.tree_bytes_exact == 0:
            return 0.0
        return self.tree_bytes_padded / self.tree_bytes_exact - 1.0

    def validate(self) -> None:
        """ppermute legality + bounds; raises AssertionError on violation.
        The unique-receiver check is CORRECTNESS here, not just
        legality: a row folded twice in one step would double-count."""
        _validate_steps(self.steps, self.buf_rows, self.tree_bytes_exact,
                        self.tree_bytes_padded)


def plan_reduce_scatterv(sizes, bucket_rounds: int = 1, segments: int = 1,
                         wave_bin_ratio: float = 0.0, validate: bool = True,
                         schedule: ComposedSchedule | None = None
                         ) -> ReduceScattervPlan:
    """Lower a reduce_scatterv schedule to fused-add ppermute steps.

    Default schedule: the packed per-segment reduction trees of
    :func:`repro.core.composed.reduce_scatterv_schedule`.  Pass the
    direct or recursive-halving schedule to race the alternatives (the
    tuner does).

    ``segments > 1`` pipelines the schedule.  Tree/direct schedules
    segment PER SEGMENT-SPAN (each owned segment's rows chunk
    independently — the alltoallv lesson: global chunks would leave
    whole segments unsplit); halving transfers carry multi-segment
    contiguous ranges, so they pipeline by GLOBAL row chunks instead.
    Correctness is unaffected either way: per-chunk rows still fold in
    their rounds' order (see :class:`ReduceScattervPlan` determinism
    note).
    """
    if schedule is None:
        schedule = reduce_scatterv_schedule(sizes)
    assert schedule.kind == "reduce_scatterv"
    # a prebuilt schedule must describe THIS problem, not a stale one
    assert (schedule.sizes[0] == np.asarray([int(s) for s in sizes])).all(), \
        "schedule was built for different segment sizes"
    sizes = tuple(int(s) for s in schedule.sizes[0])
    p = schedule.p
    total = schedule.total_rows
    cap = max(1, max(sizes, default=0))
    offsets = tuple(int(x) for x in schedule.offsets(0))
    rounds = [[(t.src, t.dst, t.size, t.start) for t in rnd]
              for rnd in schedule.rounds]
    multi_segment = any(t.lo != t.hi for rnd in schedule.rounds for t in rnd)
    if multi_segment:
        rounds = pipeline_rounds(rounds, segments, total)
    else:
        spans = [(offsets[j], offsets[j] + sizes[j])
                 for j in range(p) if sizes[j] > 0]
        rounds = pipeline_rounds_per_tree(rounds, segments, spans)
    steps, exact, padded, max_payload, stage_ids = _bucketed_steps(
        rounds, p, bucket_rounds, wave_bin_ratio)
    buf_rows = total + max(cap, max_payload)
    plan = ReduceScattervPlan(
        p, sizes, offsets, total, cap, max(1, total), buf_rows, steps,
        num_rounds=schedule.num_rounds, tree_bytes_exact=exact,
        tree_bytes_padded=padded, segments=int(segments),
        stage_ids=stage_ids,
        num_stages=_pipeline_num_stages(schedule.num_rounds, segments),
        wave_bin_ratio=float(wave_bin_ratio))
    if validate:
        plan.validate()
    return plan


@dataclass(frozen=True)
class AllreducevPlan:
    """allreducev = reduce_scatterv then allgatherv on ONE buffer.

    The post-reduce state — owner ``j``'s fully reduced block at
    ``offsets[j]`` — is EXACTLY the allgatherv start state (its
    ``in_starts`` are the same cumsum offsets), so the two step-table
    sequences concatenate with no repacking in between.  The composite
    exposes ``steps``/``stage_ids``/``padding_overhead`` etc. so the
    tuner's ``plan_step_cost``/``plan_pipeline_cost`` price it like any
    single plan.
    """

    rs: ReduceScattervPlan
    ag: ComposedPlan

    @property
    def p(self) -> int:
        return self.rs.p

    @property
    def sizes(self) -> tuple[int, ...]:
        return self.rs.sizes

    @property
    def offsets(self) -> tuple[int, ...]:
        return self.rs.offsets

    @property
    def total(self) -> int:
        return self.rs.total

    @property
    def in_rows(self) -> int:
        return self.rs.in_rows

    @property
    def buf_rows(self) -> int:
        return max(self.rs.buf_rows, self.ag.buf_rows)

    @property
    def steps(self) -> tuple[tuple, ...]:
        return self.rs.steps + self.ag.steps

    @property
    def stage_ids(self) -> tuple[int, ...]:
        # gather stages run strictly after every reduce stage completed
        shift = self.rs.num_stages
        return self.rs.stage_ids + tuple(s + shift for s in self.ag.stage_ids)

    @property
    def num_stages(self) -> int:
        return self.rs.num_stages + self.ag.num_stages

    @property
    def num_rounds(self) -> int:
        return self.rs.num_rounds + self.ag.num_rounds

    @property
    def segments(self) -> int:
        return max(self.rs.segments, self.ag.segments)

    @property
    def tree_bytes_exact(self) -> int:
        return self.rs.tree_bytes_exact + self.ag.tree_bytes_exact

    @property
    def tree_bytes_padded(self) -> int:
        return self.rs.tree_bytes_padded + self.ag.tree_bytes_padded

    @property
    def padding_overhead(self) -> float:
        if self.tree_bytes_exact == 0:
            return 0.0
        return self.tree_bytes_padded / self.tree_bytes_exact - 1.0

    def validate(self) -> None:
        self.rs.validate()
        self.ag.validate()
        assert self.rs.sizes == tuple(
            int(s) for s in np.diff(
                list(self.ag.in_starts) + [self.ag.total])), \
            "reduce and gather halves disagree on the segment layout"


def plan_allreducev(sizes, bucket_rounds: int = 1, segments: int = 1,
                    wave_bin_ratio: float = 0.0, validate: bool = True,
                    rs_schedule: ComposedSchedule | None = None,
                    ag_schedule: ComposedSchedule | None = None
                    ) -> AllreducevPlan:
    """Lower allreducev: a reduce_scatterv plan chained with an
    allgatherv plan over the same segment layout and buffer."""
    rs = plan_reduce_scatterv(sizes, bucket_rounds=bucket_rounds,
                              segments=segments,
                              wave_bin_ratio=wave_bin_ratio,
                              validate=validate, schedule=rs_schedule)
    ag = plan_allgatherv(sizes, root=None, bucket_rounds=bucket_rounds,
                         segments=segments, wave_bin_ratio=wave_bin_ratio,
                         validate=validate, schedule=ag_schedule)
    plan = AllreducevPlan(rs=rs, ag=ag)
    if validate:
        plan.validate()
    return plan


def reduce_scatterv_shard(x_local: jax.Array, plan: ReduceScattervPlan,
                          axis_name: str) -> jax.Array:
    """Per-shard reduce_scatterv body.  ``x_local``: (in_rows, F) — this
    device's full flat contribution vector (segment ``j``'s rows at
    ``offsets[j]``).  Returns (cap, F); rows [0:sizes[r]] on device ``r``
    hold ``sum_i x_i[offsets[r]: offsets[r]+sizes[r]]``."""
    r = jax.lax.axis_index(axis_name)
    offs = jnp.asarray(plan.offsets, jnp.int32)
    buf = _fill(x_local, plan.buf_rows, jnp.int32(0))
    buf = _apply_steps(buf, plan.steps, r, axis_name, reduce=True)
    with jax.named_scope(UNPACK):
        return _rows(buf, offs[r], plan.cap, x_local.shape[1])


def allreducev_shard(x_local: jax.Array, plan: AllreducevPlan,
                     axis_name: str) -> jax.Array:
    """Per-shard allreducev body.  ``x_local``: (in_rows, F) full flat
    contribution.  Returns (buf_rows, F); rows [0:total] hold the full
    reduced vector on EVERY device.  One buffer end to end: the reduce
    steps leave owner ``r``'s block at ``offsets[r]`` — allgatherv's
    start state — so the gather steps run directly on the same buffer
    with overwrite semantics."""
    r = jax.lax.axis_index(axis_name)
    buf = _fill(x_local, plan.buf_rows, jnp.int32(0))
    buf = _apply_steps(buf, plan.rs.steps, r, axis_name, reduce=True)
    return _flat(_apply_steps(buf, plan.ag.steps, r, axis_name),
                 x_local.shape[1])


# --------------------------------------------------------------------------
# host-staged execution: one program, one host layout and one runner
# --------------------------------------------------------------------------

SHARD_BODIES = {"gatherv": gatherv_shard, "scatterv": scatterv_shard,
                "allgatherv": allgatherv_shard,
                "alltoallv": alltoallv_shard,
                "reduce_scatterv": reduce_scatterv_shard,
                "allreducev": allreducev_shard}


def shard_program(op: str, plan, mesh: Mesh, axis_name):
    """The jitted SPMD program of ``op``'s shard body (``SHARD_BODIES``)
    for ``plan`` over ``mesh[axis_name]``: it takes the input
    :func:`stage` lays out and returns the output :func:`unstage` reads.
    ``axis_name`` may be an axis tuple (``("host", "device")``)."""
    body = SHARD_BODIES[op]
    return jax.jit(jax.shard_map(
        lambda xl: body(xl, plan, axis_name), mesh=mesh,
        in_specs=P(axis_name), out_specs=P(axis_name), check_vma=False))


def host_rows(op: str, data, sizes=None):
    """``(sizes, F, dtype)`` of ``op``'s host input ``data`` (see
    :func:`stage`): the true sizes, read from the blocks of gatherv,
    allgatherv and alltoallv (a p x p matrix) and given as ``sizes`` for
    scatterv and the reductions, and the rows' width and dtype."""
    if op == "alltoallv":
        sizes = [[int(b.shape[0]) for b in row] for row in data]
        rows = data[0][0]
    elif op in ("gatherv", "allgatherv"):
        sizes, rows = [int(b.shape[0]) for b in data], data[0]
    else:
        sizes = [int(s) for s in sizes]
        rows = data if op == "scatterv" else data[0]
    return sizes, int(rows.shape[1]), rows.dtype


def _place(dst: np.ndarray, blocks, strides) -> None:
    """Write ``blocks`` into ``dst`` in order, block ``k`` at row
    ``sum(strides[:k])``."""
    off = 0
    for b, q in zip(blocks, strides):
        dst[off: off + len(b)] = b
        off += int(q)


def _take(buf: np.ndarray, sizes, strides) -> np.ndarray:
    """What :func:`_place` wrote: rows ``[sum(strides[:k]),
    + sizes[k])`` of ``buf`` for every ``k``, concatenated."""
    starts = np.cumsum([0, *strides[:-1]])
    return np.concatenate([buf[s: s + int(n)]
                           for s, n in zip(starts, sizes)])


def stage(op: str, plan, data, sizes, strides) -> np.ndarray:
    """The (p * in_rows, F) input of ``op``'s shard body from the host
    arrays ``data``: gatherv and allgatherv take p blocks (sizes[i], F);
    scatterv the root's rank-ordered rows (sum(sizes), F); alltoallv p x p
    blocks, ``data[i][j]`` from rank i to rank j; the reductions p
    contribution vectors (sum(sizes), F).

    ``sizes`` are the true sizes (:func:`host_rows`) and ``strides`` the
    sizes ``plan`` was built at, of the same shape: each block sits at
    its offset in the plan and is padded with zero rows to its stride.
    A plan built at the true sizes takes them as its strides; a
    quantized one (``PlannerService``) takes the quantized sizes."""
    p = plan.p
    _, F, dtype = host_rows(op, data, sizes)
    if op == "scatterv":
        x = np.zeros((p, plan.buf_rows, F), dtype)
        _place(x[plan.root], np.split(data, np.cumsum(sizes)[:-1]), strides)
    elif op in ("reduce_scatterv", "allreducev"):
        x = np.zeros((p, plan.in_rows, F), dtype)
        for xi, c in zip(x, data):
            _place(xi, np.split(c, np.cumsum(sizes)[:-1]), strides)
    else:
        x = np.zeros((p, plan.cap, F), dtype)
        for i, xi in enumerate(x):
            if op == "alltoallv":
                _place(xi, data[i], strides[i])
            else:
                xi[: sizes[i]] = data[i]
    return x.reshape(-1, F)


def unstage(op: str, plan, out: np.ndarray, sizes, strides):
    """``op``'s result from its program's host output ``out`` (the
    true rows only, read at the offsets :func:`stage` used): gatherv the
    root's (sum(sizes), F) rows in rank order; scatterv and
    reduce_scatterv a list of rank i's (sizes[i], F) rows; allgatherv and
    allreducev (p, sum(sizes), F), every rank's copy; alltoallv a list of
    rank j's received rows, ``concat_i data[i][j]``."""
    p = plan.p
    out = out.reshape(p, -1, out.shape[-1])
    if op in ("scatterv", "reduce_scatterv"):
        return [out[i, :n] for i, n in enumerate(sizes)]
    if op == "alltoallv":
        S, Q = np.asarray(sizes), np.asarray(strides)
        return [_take(out[j], S[:, j], Q[:, j]) for j in range(p)]
    if op == "gatherv":
        return _take(out[plan.root], sizes, strides)
    return np.stack([_take(o, sizes, strides) for o in out])


def run_plan(op: str, plan, mesh: Mesh, axis_name, data, sizes=None):
    """Run ``plan``, built by ``plan_<op>`` at the true sizes, from host
    arrays over ``mesh[axis_name]``: :func:`stage`, ``device_put``,
    :func:`shard_program` under :func:`call_with_deadline`,
    :func:`unstage`.  ``data`` as :func:`stage` takes it, ``sizes`` for
    scatterv and the reductions.  Returns ``(result, plan)``."""
    sizes, _, _ = host_rows(op, data, sizes)
    if len(sizes) != mesh.devices.size:
        raise ValueError(f"{op} over {len(sizes)} ranks on a "
                         f"{mesh.devices.size}-device mesh")
    x = jax.device_put(stage(op, plan, data, sizes, sizes),
                       NamedSharding(mesh, P(axis_name)))
    fn = shard_program(op, plan, mesh, axis_name)
    out, _, _ = call_with_deadline(op, lambda: np.asarray(fn(x)))
    return unstage(op, plan, out, sizes, sizes), plan


# --------------------------------------------------------------------------
# in-graph Lemma-3 metadata protocol (scalar ppermutes, static perms)
# --------------------------------------------------------------------------

def tree_metadata_exchange(m_local: jax.Array, axis_name: str, p: int):
    """Run the fully distributed construction on DEVICE with traced sizes.

    The fixed-root pairing is rank-computable => ppermute perms are static;
    only the *contents* (estimates, gather-root ids) are traced.  Returns
    per-device (gather_root_est, gather_root_id, total) after the final
    merge — every device learns the algorithm-chosen root and the total
    bytes, in ceil(log2 p) scalar rounds, without any host involvement.

    This demonstrates Lemma 3's distributed-ness on TPU; the data plane
    still uses a host-built static plan (see module docstring).  Requires
    p to be a power of two (the general-p p-1 clamping rule lives in the
    host protocol, repro.core.distributed).
    """
    if p & (p - 1):
        raise ValueError("in-graph demo requires p = 2^k; host protocol "
                         "handles general p")
    r = jax.lax.axis_index(axis_name)
    est = jnp.zeros((), m_local.dtype)
    m_groot = m_local
    groot = r.astype(jnp.int32)
    total = m_local
    D = ceil_log2(p)
    for d in range(D):
        # cube-mirrored exchange: every member carries its cube's state, so
        # the fixed-root pairwise exchange becomes the static permutation
        # i <-> i ^ 2^d (each member talks to its mirror in the partner cube)
        perm = [(i, i ^ (1 << d)) for i in range(p)]
        o_est = jax.lax.ppermute(est, axis_name, perm)
        o_mg = jax.lax.ppermute(m_groot, axis_name, perm)
        o_gr = jax.lax.ppermute(groot, axis_name, perm)
        o_tot = jax.lax.ppermute(total, axis_name, perm)
        # decide receiver exactly like distributed._decide_lower_sends (free
        # root rule): smaller estimate sends; ties -> smaller total sends;
        # ties -> lower cube sends.
        my_lower = (r & (1 << d)) == 0
        lo_est = jnp.where(my_lower, est, o_est)
        hi_est = jnp.where(my_lower, o_est, est)
        lo_tot = jnp.where(my_lower, total, o_tot)
        hi_tot = jnp.where(my_lower, o_tot, total)
        lower_sends = jnp.where(
            lo_est != hi_est, lo_est < hi_est,
            jnp.where(lo_tot != hi_tot, lo_tot < hi_tot, True))
        take_theirs = jnp.where(my_lower, lower_sends, ~lower_sends)
        new_total = total + o_tot
        new_groot = jnp.where(take_theirs, o_gr, groot)
        new_mg = jnp.where(take_theirs, o_mg, m_groot)
        est = new_total - new_mg
        groot, m_groot, total = new_groot, new_mg, new_total
    return est, groot, total
