"""Wrappers for the ragged pack/unpack/slab kernels (gatherv pack,
scatterv unpack, per-ppermute slab copies, MoE dispatch).

Every wrapper compiles its kernel for the TPU unless the caller passes
``interpret=True``, which the CPU tests do; nothing here asks which
device is present."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (ragged_gather_kernel, ragged_scatter_kernel,
                     slab_extract_kernel, slab_fill_kernel,
                     slab_merge_add_kernel, slab_merge_kernel,
                     slab_step_kernel, slab_step_reduce_kernel,
                     slab_unpack_kernel)
from .ref import build_pack_index


def row_view(buf):
    """The (N, F // 128, 128) view of an (N, F) buffer, or (N, 1, F) when
    F is not a multiple of 128.  The TPU tiles an array's last two
    dimensions, so only in this view can the slab kernels' DMAs start at
    any row; the executor hands them this view of rows it has padded to
    :func:`lane_width`."""
    n, f = buf.shape
    lanes = 128 if f % 128 == 0 else f
    return buf.reshape(n, f // lanes, lanes)


def lane_width(f: int, dtype) -> int:
    """The row width at which the slab kernels run (N, f) rows of
    ``dtype``: the least width >= f whose :func:`row_view` the TPU lays
    out so that a DMA can start at any row.

    32-bit rows need whole 128-lane groups, which every multiple of 128
    is.  Packed rows (16- and 8-bit, ``4 // itemsize`` to a word) also
    need a group count that is a multiple of 8 or a power of two no
    smaller than the packing: in bf16, 2688 (21 groups) runs at 3072 and
    128 at 256, while 2048 and 4096 run as they are.  A width that is not
    whole lane groups is returned as it is; its (N, 1, f) view runs
    interpreted only."""
    if f % 128:
        return f
    groups = f // 128
    pack = 4 // jnp.dtype(dtype).itemsize
    if pack > 1 and groups % 8:
        groups = max(groups, pack)
        groups = (1 << (groups - 1).bit_length() if groups < 8
                  else -(-groups // 8) * 8)
    return 128 * groups


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ragged_gather(x, idx, *, block_rows: int = 128, interpret: bool = False):
    pad = (-idx.shape[0]) % block_rows
    idx_p = jnp.pad(idx, (0, pad))
    out = ragged_gather_kernel(x, idx_p, block_rows=block_rows,
                               interpret=interpret)
    return out[: idx.shape[0]]


@functools.partial(jax.jit, static_argnames=("total_pad", "block_rows",
                                             "interpret"))
def pack_blocks(blocks, sizes, total_pad: int, *, block_rows: int = 128,
                interpret: bool = False):
    """Pack padded (N, cap, F) blocks into (total_pad, F) rank order —
    the paper's zero-copy send-buffer consolidation on TPU."""
    n, cap, f = blocks.shape
    idx = build_pack_index(sizes, cap, total_pad)
    flat = jnp.concatenate([blocks.reshape(n * cap, f),
                            jnp.zeros((1, f), blocks.dtype)], axis=0)
    return ragged_gather(flat, idx, block_rows=block_rows,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_out", "block_rows",
                                             "interpret"))
def ragged_scatter(x, idx, n_out: int, *, block_rows: int = 128,
                   interpret: bool = False):
    """out[idx[i]] = x[i] over a zero (n_out, F) buffer — the unpack dual
    of :func:`ragged_gather`.  Rows whose idx is out of [0, n_out) are
    dropped onto an internal trash row."""
    pad = (-idx.shape[0]) % block_rows
    idx_p = jnp.pad(idx, (0, pad), constant_values=n_out)
    # out-of-range destinations -> internal trash row n_out (sliced off)
    idx_p = jnp.where((idx_p >= 0) & (idx_p < n_out), idx_p,
                      n_out).astype(jnp.int32)
    x_p = jnp.pad(x, ((0, pad), (0, 0)))
    out = ragged_scatter_kernel(x_p, idx_p, n_out + 1,
                                block_rows=block_rows, interpret=interpret)
    return out[:n_out]


@functools.partial(jax.jit, static_argnames=("cap", "block_rows",
                                             "interpret"))
def unpack_blocks(packed, sizes, cap: int, *, block_rows: int = 128,
                  interpret: bool = False):
    """Unpack a contiguous (total_pad, F) rank-ordered buffer into padded
    (N, cap, F) blocks — the scatterv-side inverse of
    :func:`pack_blocks`, reusing the SAME index map: pack reads flat row
    ``pack_idx[r]`` into packed row ``r``, so unpack scatters packed row
    ``r`` back to flat row ``pack_idx[r]``."""
    total_pad, f = packed.shape
    n = sizes.shape[0]
    idx = build_pack_index(sizes, cap, total_pad)  # sentinel = n*cap (trash)
    flat = ragged_scatter(packed, idx, n * cap + 1, block_rows=block_rows,
                          interpret=interpret)
    return flat[: n * cap].reshape(n, cap, f)


def _scalar(v):
    return jnp.asarray(v, jnp.int32).reshape(1)


# The slab wrappers are NOT jit-wrapped: they are called inside traced
# ``shard_map`` bodies.  ``buf`` is (N, ...) with rows on axis 0 — the
# ``row_view`` at TPU widths — and each result matches its
# ``ref.*_ref`` oracle bitwise (differentially tested).

def slab_extract(buf, start, rows: int, *, interpret: bool = False):
    """Contiguous ``rows``-row slab of ``buf`` at traced row ``start``
    (data-plane send side)."""
    return slab_extract_kernel(buf, _scalar(start), rows, interpret=interpret)


def slab_merge(buf, slab, start, valid, *, interpret: bool = False):
    """Write the ``valid``-row prefix of ``slab`` into ``buf`` at traced
    row ``start`` (data-plane receive side)."""
    return slab_merge_kernel(buf, slab, _scalar(start), _scalar(valid),
                             interpret=interpret)


def slab_step(buf, got, recv_start, recv_valid, send_start, rows_out: int, *,
              interpret: bool = False):
    """Fused step: merge the received slab ``got`` at traced row
    ``recv_start`` (``recv_valid`` live rows), then extract the next
    ``rows_out``-row outgoing slab of the MERGED buffer at traced row
    ``send_start``.  Returns ``(buf, next_slab)``."""
    return slab_step_kernel(buf, got, _scalar(recv_start),
                            _scalar(recv_valid), _scalar(send_start),
                            rows_out, interpret=interpret)


def slab_merge_add(buf, slab, start, valid, *, interpret: bool = False):
    """ADD the ``valid``-row prefix of ``slab`` into ``buf`` at traced row
    ``start`` (reduce data plane, receive side)."""
    return slab_merge_add_kernel(buf, slab, _scalar(start), _scalar(valid),
                                 interpret=interpret)


def slab_step_reduce(buf, got, recv_start, recv_valid, send_start,
                     rows_out: int, *, interpret: bool = False):
    """Fused reduce step: fold the received slab ``got`` into the
    accumulator at traced row ``recv_start`` (``recv_valid`` live rows,
    ADD not overwrite), then extract the next ``rows_out``-row outgoing
    partial sum of the UPDATED buffer at traced row ``send_start``.
    Returns ``(buf, next_slab)``."""
    return slab_step_reduce_kernel(buf, got, _scalar(recv_start),
                                   _scalar(recv_valid), _scalar(send_start),
                                   rows_out, interpret=interpret)


def slab_fill(x, buf_rows: int, start, *, interpret=False):
    """A fresh ``buf_rows``-row buffer holding ``x`` at traced row
    ``start``; its other rows are uninitialised, so only a caller that
    reads nothing of them unmasked may use it (alltoallv's capacity
    buffer)."""
    return slab_fill_kernel(x, buf_rows, _scalar(start), interpret=interpret)


def slab_unpack(buf, src_start, dst_start, valid, chunk: int, out_rows: int,
                *, interpret=False):
    """A fresh ``out_rows``-row buffer holding, for each block i, the
    ``valid[i]`` rows of ``buf`` from traced row ``src_start[i]`` at traced
    row ``dst_start[i]`` (``valid[i] <= chunk``); its other rows are
    uninitialised, so only a caller that masks them may use it
    (alltoallv's output)."""
    return slab_unpack_kernel(
        buf, *(jnp.asarray(t, jnp.int32) for t in (src_start, dst_start,
                                                    valid)),
        chunk, out_rows, interpret=interpret)
