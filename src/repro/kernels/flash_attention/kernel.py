"""Pallas TPU flash attention (blocked online softmax).

Grid (B*H, n_q_blocks, n_kv_blocks); the kv dimension is 'arbitrary'
(sequential) and accumulates into VMEM scratch (m, l, acc) per q block —
the canonical TPU formulation: q/k/v tiles sized for VMEM, matmul dims
128-aligned for the MXU.  Causal and sliding-window masks skip fully
masked kv blocks via pl.when; GQA maps q-head -> kv-head in the kv
BlockSpec index_map (no materialized head broadcast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, n_k: int, causal: bool,
            window: int | None, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # block-level skip: fully-masked kv blocks do no work
    run = jnp.asarray(True)
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1
                              > q_start - window)

    @pl.when(run)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                            block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                            block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p, v))
        m_ref[...] = m_cur

    @pl.when(ik == n_k - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B,H,T,hd), k/v: (B,Hkv,S,hd) -> (B,H,T,hd)."""
    b, h, t, hd = q.shape
    _, hkv, s, _ = k.shape
    assert t % block_q == 0 and s % block_k == 0
    group = h // hkv
    grid = (b * h, t // block_q, s // block_k)
    scale = 1.0 / (hd ** 0.5)

    def qmap(bh, iq, ik):
        return (bh // h, bh % h, iq, 0)

    def kvmap(bh, iq, ik):
        return (bh // h, (bh % h) // group, ik, 0)

    kern = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_k=s // block_k,
        causal=causal, window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), qmap),
            pl.BlockSpec((1, 1, block_k, hd), kvmap),
            pl.BlockSpec((1, 1, block_k, hd), kvmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
