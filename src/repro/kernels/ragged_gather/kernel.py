"""Pallas TPU kernels for the ragged pack/unpack/slab data plane.

* ``ragged_gather_kernel`` — tiled ragged row gather out[i] = x[idx[i]]
  (pack).  OUTPUT-TILE-CENTRIC: each grid step owns one (block_rows, F)
  output tile (disjoint writes, MXU/VPU-aligned), and the row-index map
  ``idx`` is scalar-prefetched into SMEM so the source row of every
  output row is known before the tile executes.  x stays resident in
  VMEM.
* ``ragged_scatter_kernel`` — the inverse unpack out[idx[i]] = x[i].
  INPUT-TILE-CENTRIC: each grid step owns one (block_rows, F) tile of x
  and stores its rows at their (prefetched) destinations; the output is
  zero-initialized by the first grid step and revisited by later ones
  (TPU grids are sequential, so the read-modify-write order is defined).
  Out-of-range destinations land on a caller-provided trash row.
* ``slab_extract_kernel`` / ``slab_merge_kernel`` — the per-ppermute
  slab copies of the gatherv/scatterv data plane: read ``rows``
  contiguous rows at a DYNAMIC (traced, per-device) offset, and write
  the valid prefix of a received slab back at its receive offset.  The
  offsets arrive as scalar-prefetch arguments, so inside ``shard_map``
  each device runs the same program with its own table-looked-up
  starts.  The buffer stays in HBM and is updated in place by DMA.
* ``slab_step_kernel`` — the FUSED step of the executor loop: one
  invocation merges the slab received by the previous ppermute and then
  reads the NEXT outgoing slab from the merged buffer (the extract must
  observe the merge — a forwarded range can contain rows that just
  arrived).
* ``slab_merge_add_kernel`` / ``slab_step_reduce_kernel`` — the same
  with the received rows ADDED into the buffer (reduce data plane),
  folded through VMEM one row tile at a time.
* ``slab_fill_kernel`` — a fresh capacity buffer holding the input at a
  dynamic row: the input's rows are DMAed in and nothing else is
  written, so every other row is left uninitialised (the executor uses
  it only where no such row can be read unmasked).
* ``slab_unpack_kernel`` — a fresh output holding the valid prefix of
  each of p source blocks of the buffer, each at its own dynamic
  destination row, by DMA alone; every other row is left uninitialised
  (alltoallv's output, whose caller masks the rows past its valid count).

Every ``pallas_call`` carries a stable ``name=`` from ``KERNEL_NAMES``.
The name becomes the HLO custom-call instruction's name, so a device
trace shows each kernel as ``<name>/custom-call`` instead of under the
name of the function that called it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import fold_add

# The HLO instruction name of each kernel (``pallas_call(name=...)``).
KERNEL_NAMES = ("slab_extract", "slab_merge", "slab_step", "slab_merge_add",
                "slab_step_reduce", "slab_fill", "slab_unpack",
                "ragged_gather", "ragged_scatter")


def _kernel(idx_ref, x_ref, o_ref, *, block_rows: int):
    t = pl.program_id(0)

    def body(r, _):
        src = idx_ref[t * block_rows + r]
        src = jnp.clip(src, 0, x_ref.shape[0] - 1)
        o_ref[pl.ds(r, 1), :] = x_ref[pl.ds(src, 1), :]
        return 0

    jax.lax.fori_loop(0, block_rows, body, 0)


def ragged_gather_kernel(x: jax.Array, idx: jax.Array, *,
                         block_rows: int = 128,
                         interpret: bool = False) -> jax.Array:
    """x: (N, F) resident rows; idx: (M,) int32 (padded to block_rows).
    Returns (M, F) with out[i] = x[idx[i]] (idx clipped into range)."""
    m = idx.shape[0]
    f = x.shape[1]
    assert m % block_rows == 0, "pad idx to a multiple of block_rows"
    grid = (m // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # idx lives in SMEM
            grid=grid,
            # index maps receive (*grid, *scalar_prefetch_refs)
            in_specs=[pl.BlockSpec(x.shape, lambda t, idx: (0, 0))],
            out_specs=pl.BlockSpec((block_rows, f), lambda t, idx: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        interpret=interpret,
        name="ragged_gather",
    )(idx, x)


def _scatter_kernel(idx_ref, x_ref, o_ref, *, block_rows: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(r, _):
        dst = idx_ref[t * block_rows + r]
        dst = jnp.clip(dst, 0, o_ref.shape[0] - 1)
        o_ref[pl.ds(dst, 1), :] = x_ref[pl.ds(r, 1), :]
        return 0

    jax.lax.fori_loop(0, block_rows, body, 0)


def ragged_scatter_kernel(x: jax.Array, idx: jax.Array, n_out: int, *,
                          block_rows: int = 128,
                          interpret: bool = False) -> jax.Array:
    """x: (M, F) rows; idx: (M,) int32 (padded to block_rows).  Returns
    (n_out, F) zero-initialized with out[idx[i]] = x[i] (idx clipped into
    range; callers point padding rows at a trash row ``n_out - 1`` or pass
    an ``n_out`` one larger than the live range).  Duplicate destinations
    resolve to the LAST writer in row order (the grid is sequential)."""
    m = idx.shape[0]
    f = x.shape[1]
    assert m % block_rows == 0, "pad idx to a multiple of block_rows"
    grid = (m // block_rows,)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # idx lives in SMEM
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, f), lambda t, idx: (t, 0))],
            # whole output resident: every grid step may touch any row
            out_specs=pl.BlockSpec((n_out, f), lambda t, idx: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, f), x.dtype),
        interpret=interpret,
        name="ragged_scatter",
    )(idx, x)


# --------------------------------------------------------------------------
# slab kernels of the SPMD data plane
# --------------------------------------------------------------------------
#
# The capacity buffer stays in HBM (``pl.ANY``) and is aliased from input
# to output, so no step copies it whole: only slab rows move, by DMA.  The
# TPU tiles the last two dimensions of an array, so a DMA may start only
# at a multiple of 8 rows of a 2-D (N, F) buffer; the executor therefore
# passes the row view (N, F // 128, 128) (``ops.row_view``), in which a
# row is a major index and a DMA may start at any row.  A row count known
# only at run time (the valid prefix of a received slab) moves as one
# static-size DMA per set bit of the count.

_ANY = pl.BlockSpec(memory_space=pl.ANY)
_FOLD_TILE_BYTES = 1 << 20   # VMEM bytes per operand tile of the add kernels


def _pow2_chunks(rows: int) -> list[int]:
    """DMA sizes (descending powers of two) whose subsets sum to every
    count in [0, rows]."""
    return [1 << b for b in reversed(range(rows.bit_length()))]


def _extract(src, s0, out, sem):
    """out <- src[s0 : s0 + out.shape[0]]."""
    copy = pltpu.make_async_copy(src.at[pl.ds(s0, out.shape[0])], out, sem)
    copy.start()
    copy.wait()


def _prefix_copies(src, s0, dst, d0, n, rows: int, sems, sem0: int = 0):
    """The DMAs of dst[d0 : d0 + n] <- src[s0 : s0 + n] for a traced
    n <= rows, one per set bit of n, on semaphores ``sem0, sem0 + 1, ...``
    of ``sems``: a list of ``(bit, copy)``, to run where ``bit != 0``."""
    copies = []
    off = jnp.int32(0)
    for i, size in enumerate(_pow2_chunks(rows)):
        bit = n & size
        copies.append((bit, pltpu.make_async_copy(
            src.at[pl.ds(s0 + off, size)], dst.at[pl.ds(d0 + off, size)],
            sems.at[sem0 + i])))
        off = off + bit
    return copies


def _run_copies(copies):
    """Start every copy of ``_prefix_copies`` whose bit is set, then wait
    for them all."""
    for bit, copy in copies:
        pl.when(bit != 0)(copy.start)
    for bit, copy in copies:
        pl.when(bit != 0)(copy.wait)


def _copy_prefix(src, dst, d0, n, sems):
    """dst[d0 : d0 + n] <- src[0 : n] for a traced n <= src rows: one DMA
    per set bit of n, all started before any is waited on."""
    _run_copies(_prefix_copies(src, 0, dst, d0, n, src.shape[0], sems))


def _fold_prefix(src, dst, d0, n, cur, inc, sems):
    """dst[d0 + i] += src[i] for i < n (traced), one VMEM tile of
    ``cur.shape[0]`` rows at a time.  Rows >= n keep dst's bits: the mask
    selects them unmodified (cur + 0 would flip -0.0 to +0.0)."""
    rows, tile = src.shape[0], cur.shape[0]

    def body(t, carry):
        lo = t * tile
        c0 = jnp.minimum(lo, rows - tile)  # the last tile slides back in
        read_dst = pltpu.make_async_copy(dst.at[pl.ds(d0 + c0, tile)], cur,
                                         sems.at[0])
        read_src = pltpu.make_async_copy(src.at[pl.ds(c0, tile)], inc,
                                         sems.at[1])
        read_dst.start()
        read_src.start()
        read_dst.wait()
        read_src.wait()
        g = c0 + jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
        x = cur[...]
        # rows a slid-back tile shares with the previous one are folded
        cur[...] = jnp.where((g >= lo) & (g < n), fold_add(x, inc[...]), x)
        write = pltpu.make_async_copy(cur, dst.at[pl.ds(d0 + c0, tile)],
                                      sems.at[0])
        write.start()
        write.wait()
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, tile), body, 0)


def _fold_scratch(buf, rows: int):
    row_bytes = buf.dtype.itemsize * math.prod(buf.shape[1:])
    tile = max(1, min(rows, _FOLD_TILE_BYTES // row_bytes))
    shape = (tile,) + buf.shape[1:]
    return [pltpu.VMEM(shape, buf.dtype), pltpu.VMEM(shape, buf.dtype),
            pltpu.SemaphoreType.DMA((2,))]


def _slab_call(name, body, scalars, tensors, out_shape, scratch, *,
               in_place: bool, interpret: bool):
    """One-program pallas_call named ``name`` over HBM operands.
    ``in_place`` aliases the first tensor (the buffer) to the first
    output."""
    n_out = len(out_shape) if isinstance(out_shape, tuple) else 1
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[_ANY] * len(tensors),
            out_specs=[_ANY] * n_out if n_out > 1 else _ANY,
            scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases={len(scalars): 0} if in_place else {},
        interpret=interpret,
        name=name,
    )(*scalars, *tensors)


def _slab_extract_kernel(start_ref, buf, out, sem):
    _extract(buf, start_ref[0], out, sem)


def slab_extract_kernel(buf: jax.Array, start: jax.Array, rows: int, *,
                        interpret: bool = False) -> jax.Array:
    """The ``rows``-row slab of ``buf`` at dynamic row ``start``, a (1,)
    int32 array (typically a traced per-device value inside
    ``shard_map``) prefetched to SMEM."""
    return _slab_call(
        "slab_extract", _slab_extract_kernel, (start,), (buf,),
        jax.ShapeDtypeStruct((rows,) + buf.shape[1:], buf.dtype),
        [pltpu.SemaphoreType.DMA(())], in_place=False, interpret=interpret)


def _slab_merge_kernel(start_ref, valid_ref, buf_in, slab, buf, sems):
    del buf_in  # aliased to buf
    _copy_prefix(slab, buf, start_ref[0], valid_ref[0], sems)


def slab_merge_kernel(buf: jax.Array, slab: jax.Array, start: jax.Array,
                      valid: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """Write the ``valid``-row prefix of ``slab`` into ``buf`` at dynamic
    row ``start`` (rows >= valid keep buf's data), in place.  ``start``
    and ``valid`` are (1,) int32 arrays (traced per-device values)."""
    return _slab_call(
        "slab_merge", _slab_merge_kernel, (start, valid), (buf, slab),
        jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        [pltpu.SemaphoreType.DMA((len(_pow2_chunks(slab.shape[0])),))],
        in_place=True, interpret=interpret)


def _slab_step_kernel(recv_ref, valid_ref, send_ref, buf_in, slab, buf, out,
                      sems):
    del buf_in  # aliased to buf
    _copy_prefix(slab, buf, recv_ref[0], valid_ref[0], sems)
    # extract AFTER the merge landed: the outgoing slab may overlap the
    # range that was just received (tree forwarding)
    _extract(buf, send_ref[0], out, sems.at[0])


def slab_step_kernel(buf: jax.Array, slab: jax.Array, recv_start: jax.Array,
                     recv_valid: jax.Array, send_start: jax.Array,
                     rows_out: int, *,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused merge-then-extract: merge the ``recv_valid``-row prefix of
    ``slab`` into ``buf`` at dynamic row ``recv_start``, and return
    ``(merged_buf, next_slab)`` where ``next_slab`` is the contiguous
    ``rows_out``-row slab of the MERGED buffer at dynamic row
    ``send_start``.  All three scalars are (1,) int32 arrays (traced
    per-device values looked up from the step tables)."""
    return _slab_call(
        "slab_step", _slab_step_kernel, (recv_start, recv_valid, send_start),
        (buf, slab),
        (jax.ShapeDtypeStruct(buf.shape, buf.dtype),
         jax.ShapeDtypeStruct((rows_out,) + buf.shape[1:], buf.dtype)),
        [pltpu.SemaphoreType.DMA((len(_pow2_chunks(slab.shape[0])),))],
        in_place=True, interpret=interpret)


def _slab_merge_add_kernel(start_ref, valid_ref, buf_in, slab, buf, cur, inc,
                           sems):
    del buf_in  # aliased to buf
    _fold_prefix(slab, buf, start_ref[0], valid_ref[0], cur, inc, sems)


def slab_merge_add_kernel(buf: jax.Array, slab: jax.Array, start: jax.Array,
                          valid: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """ADD the ``valid``-row prefix of ``slab`` into ``buf`` at dynamic
    row ``start``, in place (rows >= valid keep buf's data bit-exactly).
    The reduction dual of ``slab_merge_kernel``."""
    return _slab_call(
        "slab_merge_add", _slab_merge_add_kernel, (start, valid),
        (buf, slab),
        jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        _fold_scratch(buf, slab.shape[0]), in_place=True,
        interpret=interpret)


def _slab_step_reduce_kernel(recv_ref, valid_ref, send_ref, buf_in, slab,
                             buf, out, cur, inc, sems):
    del buf_in  # aliased to buf
    _fold_prefix(slab, buf, recv_ref[0], valid_ref[0], cur, inc, sems)
    # extract AFTER the fold landed: a root-ward forward carries the
    # partial sum including the contribution that just arrived
    _extract(buf, send_ref[0], out, sems.at[0])


def slab_step_reduce_kernel(buf: jax.Array, slab: jax.Array,
                            recv_start: jax.Array, recv_valid: jax.Array,
                            send_start: jax.Array, rows_out: int, *,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array]:
    """Fused reduce-dataplane step: ADD the ``recv_valid``-row prefix of
    ``slab`` into ``buf`` at dynamic row ``recv_start``, and return
    ``(updated_buf, next_slab)`` where ``next_slab`` is the
    ``rows_out``-row slab of the UPDATED buffer at dynamic row
    ``send_start``."""
    return _slab_call(
        "slab_step_reduce", _slab_step_reduce_kernel,
        (recv_start, recv_valid, send_start), (buf, slab),
        (jax.ShapeDtypeStruct(buf.shape, buf.dtype),
         jax.ShapeDtypeStruct((rows_out,) + buf.shape[1:], buf.dtype)),
        _fold_scratch(buf, slab.shape[0]), in_place=True,
        interpret=interpret)


def _slab_fill_kernel(start_ref, x, buf, sem):
    copy = pltpu.make_async_copy(x, buf.at[pl.ds(start_ref[0], x.shape[0])],
                                 sem)
    copy.start()
    copy.wait()


def slab_fill_kernel(x: jax.Array, buf_rows: int, start: jax.Array, *,
                     interpret: bool | pltpu.InterpretParams = False
                     ) -> jax.Array:
    """A fresh ``(buf_rows,) + x.shape[1:]`` buffer whose rows
    ``[start, start + x.shape[0])`` are ``x``, moved by one DMA;
    ``start`` is a (1,) int32 array (a traced per-device value).
    Nothing else is written: every other row is uninitialised (NaN under
    ``pltpu.InterpretParams(uninitialized_memory="nan")``)."""
    return _slab_call(
        "slab_fill", _slab_fill_kernel, (start,), (x,),
        jax.ShapeDtypeStruct((buf_rows,) + x.shape[1:], x.dtype),
        [pltpu.SemaphoreType.DMA(())], in_place=False, interpret=interpret)


def _slab_unpack_kernel(src_ref, dst_ref, valid_ref, buf, out, sems, *,
                        chunk: int):
    bits = len(_pow2_chunks(chunk))
    copies = []
    for i in range(src_ref.shape[0]):
        copies += _prefix_copies(buf, src_ref[i], out, dst_ref[i],
                                 valid_ref[i], chunk, sems, sem0=i * bits)
    # every block's DMAs are in flight before the first wait
    _run_copies(copies)


def slab_unpack_kernel(buf: jax.Array, src_start: jax.Array,
                       dst_start: jax.Array, valid: jax.Array, chunk: int,
                       out_rows: int, *,
                       interpret: bool | pltpu.InterpretParams = False
                       ) -> jax.Array:
    """A fresh ``(out_rows,) + buf.shape[1:]`` array whose rows
    ``[dst_start[i], dst_start[i] + valid[i])`` are ``buf``'s rows
    ``[src_start[i], src_start[i] + valid[i])``, for each block i of the
    three (blocks,) int32 tables (traced per-device values), with
    ``valid[i] <= chunk``.  DMA alone, one per set bit of each count, all
    started before any is waited on; every other row is uninitialised
    (NaN under ``pltpu.InterpretParams(uninitialized_memory="nan")``)."""
    blocks = src_start.shape[0]
    return _slab_call(
        "slab_unpack", functools.partial(_slab_unpack_kernel, chunk=chunk),
        (src_start, dst_start, valid), (buf,),
        jax.ShapeDtypeStruct((out_rows,) + buf.shape[1:], buf.dtype),
        [pltpu.SemaphoreType.DMA((blocks * len(_pow2_chunks(chunk)),))],
        in_place=False, interpret=interpret)
