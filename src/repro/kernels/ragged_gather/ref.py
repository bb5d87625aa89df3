"""Pure-jnp oracles for the ragged pack/unpack/slab kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fold_add(cur, inc):
    """``cur + inc`` in ``cur``'s dtype.  Floats narrower than 32 bits
    are summed in float32 and rounded once, as the TPU's vector unit,
    XLA and ml_dtypes' NumPy arrays all do, so the kernels, this
    reference and the NumPy oracle round every fold identically."""
    if jnp.issubdtype(cur.dtype, jnp.floating) and cur.dtype.itemsize < 4:
        return (cur.astype(jnp.float32) + inc.astype(jnp.float32)
                ).astype(cur.dtype)
    return cur + inc


def ragged_gather_ref(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """out[i] = x[idx[i]].  idx rows out of range read row 0 (callers use a
    zero row-0 sentinel for padding)."""
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    return jnp.take(x, safe, axis=0)


def ragged_scatter_ref(x: jnp.ndarray, idx: jnp.ndarray,
                       n_out: int) -> jnp.ndarray:
    """out[idx[i]] = x[i] over a zero (n_out, F) buffer.  Same contract as
    ``ops.ragged_scatter``: rows whose idx is outside [0, n_out) are
    DROPPED (routed to a trash row, sliced off).  Duplicate in-range
    destinations are unspecified-order in both implementations — the
    data-plane index maps are injective, so callers never rely on it."""
    safe = jnp.where((idx >= 0) & (idx < n_out), idx, n_out)
    out = jnp.zeros((n_out + 1, x.shape[1]), x.dtype)
    return out.at[safe].set(x, mode="drop", unique_indices=False)[:n_out]


def slab_extract_ref(buf: jnp.ndarray, start, rows: int) -> jnp.ndarray:
    """Contiguous (rows, F) slab of ``buf`` at (possibly traced) row
    ``start``."""
    start = jnp.asarray(start, jnp.int32).reshape(())
    return jax.lax.dynamic_slice(buf, (start, jnp.int32(0)),
                                 (rows, buf.shape[1]))


def slab_merge_ref(buf: jnp.ndarray, slab: jnp.ndarray, start,
                   valid) -> jnp.ndarray:
    """Merge the ``valid``-row prefix of ``slab`` into ``buf`` at row
    ``start``; rows >= valid keep buf's data."""
    start = jnp.asarray(start, jnp.int32).reshape(())
    valid = jnp.asarray(valid, jnp.int32).reshape(())
    rows = slab.shape[0]
    cur = jax.lax.dynamic_slice(buf, (start, jnp.int32(0)),
                                (rows, buf.shape[1]))
    mask = (jnp.arange(rows, dtype=jnp.int32) < valid)[:, None]
    return jax.lax.dynamic_update_slice(buf, jnp.where(mask, slab, cur),
                                        (start, jnp.int32(0)))


def slab_step_ref(buf: jnp.ndarray, got: jnp.ndarray, recv_start,
                  recv_valid, send_start,
                  rows_out: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused dataplane step: merge the received slab, then extract the
    next outgoing slab FROM THE MERGED buffer (a forwarded slab may
    contain rows that just arrived).  Semantically exactly
    ``slab_merge_ref`` followed by ``slab_extract_ref`` — the Pallas
    ``slab_step_kernel`` must match this oracle row-identically."""
    buf = slab_merge_ref(buf, got, recv_start, recv_valid)
    return buf, slab_extract_ref(buf, send_start, rows_out)


def slab_merge_add_ref(buf: jnp.ndarray, slab: jnp.ndarray, start,
                       valid) -> jnp.ndarray:
    """ADD the ``valid``-row prefix of ``slab`` into ``buf`` at row
    ``start``; rows >= valid keep buf's data unchanged.  The reduction
    dual of ``slab_merge_ref`` — masked rows select ``cur`` outright (not
    ``cur + 0``, which would rewrite ``-0.0`` as ``+0.0``), so the
    accumulator stays bitwise untouched outside the live prefix."""
    start = jnp.asarray(start, jnp.int32).reshape(())
    valid = jnp.asarray(valid, jnp.int32).reshape(())
    rows = slab.shape[0]
    cur = jax.lax.dynamic_slice(buf, (start, jnp.int32(0)),
                                (rows, buf.shape[1]))
    mask = (jnp.arange(rows, dtype=jnp.int32) < valid)[:, None]
    # masked rows select cur outright (cur + 0 would flip -0.0 to +0.0)
    return jax.lax.dynamic_update_slice(
        buf, jnp.where(mask, fold_add(cur, slab), cur), (start, jnp.int32(0)))


def slab_step_reduce_ref(buf: jnp.ndarray, got: jnp.ndarray, recv_start,
                         recv_valid, send_start,
                         rows_out: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused REDUCTION dataplane step: fold the received slab into the
    accumulator (add, not overwrite), then extract the next outgoing
    partial sum FROM THE UPDATED buffer — a root-ward forward must carry
    the contribution that just arrived.  Semantically exactly
    ``slab_merge_add_ref`` followed by ``slab_extract_ref``; the Pallas
    ``slab_step_reduce_kernel`` must match this oracle bitwise."""
    buf = slab_merge_add_ref(buf, got, recv_start, recv_valid)
    return buf, slab_extract_ref(buf, send_start, rows_out)


def slab_unpack_ref(buf: jnp.ndarray, src_start, dst_start, valid,
                    chunk: int, out_rows: int) -> jnp.ndarray:
    """A zero ``(out_rows,) + buf.shape[1:]`` array whose rows
    ``[dst_start[i], dst_start[i] + valid[i])`` are ``buf``'s rows
    ``[src_start[i], src_start[i] + valid[i])``, block by block, for
    ``valid[i] <= chunk``: ``chunk`` rows are cut from ``buf`` and
    written under a mask of the valid ones, so every start plus ``chunk``
    must lie within its array (``ComposedPlan.validate``'s bounds)."""
    out = jnp.zeros((out_rows,) + buf.shape[1:], buf.dtype)
    mask = jnp.arange(chunk, dtype=jnp.int32).reshape(
        (chunk,) + (1,) * (buf.ndim - 1))
    zeros = (jnp.int32(0),) * (buf.ndim - 1)
    for s0, d0, nv in zip(src_start, dst_start, valid):
        blk = jax.lax.dynamic_slice(buf, (s0,) + zeros,
                                    (chunk,) + buf.shape[1:])
        cur = jax.lax.dynamic_slice(out, (d0,) + zeros,
                                    (chunk,) + buf.shape[1:])
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(mask < nv, blk, cur), (d0,) + zeros)
    return out


def pack_blocks_ref(blocks: jnp.ndarray, sizes: jnp.ndarray,
                    total_pad: int) -> jnp.ndarray:
    """Pack padded (N, cap, F) blocks into a contiguous (total_pad, F)
    buffer in rank order (the paper's send-buffer consolidation)."""
    n, cap, f = blocks.shape
    idx = build_pack_index(sizes, cap, total_pad)
    flat = blocks.reshape(n * cap, f)
    zero = jnp.zeros((1, f), blocks.dtype)
    src = jnp.concatenate([flat, zero], axis=0)
    return jnp.take(src, idx, axis=0)


def build_pack_index(sizes: jnp.ndarray, cap: int, total_pad: int):
    """Row-index map for the pack: output row r (inside block b at offset
    o) reads flat row b*cap + o; padding rows read the zero sentinel."""
    n = sizes.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                               jnp.cumsum(sizes)[:-1]])
    r = jnp.arange(total_pad)
    b = jnp.searchsorted(jnp.cumsum(sizes), r, side="right")
    b = jnp.clip(b, 0, n - 1)
    o = r - offsets[b]
    valid = (o >= 0) & (o < sizes[b]) & (r < jnp.sum(sizes))
    flat_idx = b * cap + o
    sentinel = n * cap  # the appended zero row
    return jnp.where(valid, flat_idx, sentinel).astype(jnp.int32)
