"""Pallas TPU kernel: blocked RG-LRU linear recurrence.

Grid (B_tiles, D_tiles, T_chunks); the chunk dimension is sequential
('arbitrary') and carries h in VMEM scratch.  Within a chunk the
recurrence runs as an in-register fori_loop over rows — D is the vector
lane dimension (128-aligned), so each step is one VPU multiply-add over
the (block_b, block_d) tile: the memory-bound pattern RecurrentGemma's
TPU kernel targets (HBM traffic = read a,b once, write h once).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, carry_ref, *,
            chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def init():
        carry_ref[...] = h0_ref[...]

    def body(t, h):
        h = a_ref[:, t, :] * h + b_ref[:, t, :]
        o_ref[:, t, :] = h
        return h

    h = jax.lax.fori_loop(0, chunk, body, carry_ref[...])
    carry_ref[...] = h

    @pl.when(ic == n_chunks - 1)
    def fin():
        hlast_ref[...] = h


def rglru_scan_kernel(a, b, h0, *, block_b: int = 8, block_d: int = 128,
                      chunk: int = 256, interpret: bool = False):
    """a, b: (B,T,D) f32; h0: (B,D) f32 -> (h (B,T,D), h_last (B,D))."""
    B, T, D = a.shape
    assert B % block_b == 0 and D % block_d == 0 and T % chunk == 0
    grid = (B // block_b, D // block_d, T // chunk)

    def abmap(ib, id_, ic):
        return (ib, ic, id_)

    def hmap(ib, id_, ic):
        return (ib, id_)

    kern = functools.partial(_kernel, chunk=chunk, n_chunks=T // chunk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, chunk, block_d), abmap),
            pl.BlockSpec((block_b, chunk, block_d), abmap),
            pl.BlockSpec((block_b, block_d), hmap),
        ],
        out_specs=[
            pl.BlockSpec((block_b, chunk, block_d), abmap),
            pl.BlockSpec((block_b, block_d), hmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, D), a.dtype),
            jax.ShapeDtypeStruct((B, D), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
