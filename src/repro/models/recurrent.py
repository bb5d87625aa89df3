"""Recurrent blocks: RG-LRU (RecurrentGemma/Griffin), mLSTM and sLSTM
(xLSTM).  Training paths are TPU-adapted: RG-LRU uses an associative scan
(log-depth), mLSTM uses its parallel stabilized attention form, sLSTM is a
true recurrence (lax.scan) — the xLSTM paper uses a custom CUDA kernel
there; on TPU the sequential scan is the honest equivalent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import trunc_normal

_C_RGLRU = 8.0


# --------------------------------------------------------------- RG-LRU

def init_rglru(key, d_model, dtype, conv_width=4):
    ks = jax.random.split(key, 6)
    d = d_model
    return {
        "wx": trunc_normal(ks[0], (d, d), 1.0, dtype),    # recurrent branch
        "wg": trunc_normal(ks[1], (d, d), 1.0, dtype),    # gate branch
        "wo": trunc_normal(ks[2], (d, d), 1.0, dtype),
        "conv": trunc_normal(ks[3], (conv_width, d), 1.0, dtype),
        "wa": trunc_normal(ks[4], (d, d), 1.0, dtype),    # recurrence gate r_t
        "wi": trunc_normal(ks[5], (d, d), 1.0, dtype),    # input gate i_t
        "lam": jnp.full((d,), 2.2, dtype),                # a = sigmoid(lam)
    }


def _rglru_coeffs(p, u):
    """u: (B,T,D) post-conv recurrent branch.  Returns (a, b) of the linear
    recurrence h_t = a_t * h_{t-1} + b_t, computed in f32."""
    r = jax.nn.sigmoid((u @ p["wa"].astype(u.dtype)).astype(jnp.float32))
    i = jax.nn.sigmoid((u @ p["wi"].astype(u.dtype)).astype(jnp.float32))
    log_a = -_C_RGLRU * r * jax.nn.softplus(p["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
        i * u.astype(jnp.float32))
    return a, b


def _causal_conv(p, x, state=None):
    """Width-W causal depthwise conv.  state: (B, W-1, D) trailing inputs."""
    w = p["conv"].astype(jnp.float32)
    W = w.shape[0]
    x32 = x.astype(jnp.float32)
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), jnp.float32)
    else:
        pad = state.astype(jnp.float32)
    xp = jnp.concatenate([pad, x32], axis=1)
    out = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):]
    return out.astype(x.dtype), new_state


def _assoc_scan(a, b):
    """Inclusive scan of h_t = a_t h_{t-1} + b_t with h_0 = 0 over axis 1.
    Returns (A, h): A_t = prod_{j<=t} a_j (for chunk h0 injection)."""
    def comb(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2
    return jax.lax.associative_scan(comb, (a, b), axis=1)


def rglru_block(p, x, state=None, chunk=256):
    """x: (B,T,D).  state: None (train) or {'h': (B,D), 'conv': (B,W-1,D)}.
    Returns (out, new_state).

    Long sequences scan over chunks of ``chunk`` (associative scan within a
    chunk, h0 injected via the chunk's cumulative decay A): O(chunk)
    transient memory instead of O(T) scan intermediates — the TPU-friendly
    blocking of the linear recurrence."""
    g = jax.nn.gelu(x @ p["wg"].astype(x.dtype))
    u = x @ p["wx"].astype(x.dtype)
    u, conv_state = _causal_conv(p, u, None if state is None else state["conv"])
    a, b = _rglru_coeffs(p, u)
    if state is None:
        B, T, D = x.shape
        if T > 2 * chunk and T % chunk == 0:
            n = T // chunk
            ar = a.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
            br = b.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)

            def step(h0, ab):
                ac, bc = ab
                A, hc = _assoc_scan(ac, bc)
                hc = hc + A * h0[:, None]
                return hc[:, -1], hc
            new_h, hs = jax.lax.scan(step, jnp.zeros((B, D), jnp.float32),
                                     (ar, br))
            h = hs.transpose(1, 0, 2, 3).reshape(B, T, D)
        else:
            _, h = _assoc_scan(a, b)
            new_h = h[:, -1]
    else:
        h0 = state["h"].astype(jnp.float32)
        h = a[:, 0] * h0 + b[:, 0]
        new_h = h
        h = h[:, None]
    out = (h.astype(x.dtype) * g) @ p["wo"].astype(x.dtype)
    return out, {"h": new_h, "conv": conv_state}


def rglru_init_state(batch, d_model, dtype, conv_width=4):
    return {"h": jnp.zeros((batch, d_model), jnp.float32),
            "conv": jnp.zeros((batch, conv_width - 1, d_model), dtype)}


# ---------------------------------------------------------------- mLSTM

def init_mlstm(key, d_model, n_heads, dtype):
    ks = jax.random.split(key, 7)
    d = d_model
    hd = d // n_heads
    return {
        "wq": trunc_normal(ks[0], (d, d), 1.0, dtype),
        "wk": trunc_normal(ks[1], (d, d), 1.0, dtype),
        "wv": trunc_normal(ks[2], (d, d), 1.0, dtype),
        "wi": trunc_normal(ks[3], (d, n_heads), 1.0, dtype),  # input gate
        "wf": trunc_normal(ks[4], (d, n_heads), 1.0, dtype),  # forget gate
        "wg": trunc_normal(ks[5], (d, d), 1.0, dtype),        # output gate
        "wo": trunc_normal(ks[6], (d, d), 1.0, dtype),
    }


def _mlstm_chunkwise(q, k, v, log_i, log_f, chunk):
    """Chunkwise-parallel mLSTM (the xLSTM paper's training algorithm,
    TPU-adapted): intra-chunk parallel stabilized form + inter-chunk
    recurrent (C, n, m) state.  O(T * chunk) instead of O(T^2).

    q,k,v: (B,T,H,hd) (k pre-scaled); gates (B,T,H) f32.
    Returns (h (B,T,H,hd) f32, final state dict)."""
    B, T, H, hd = q.shape
    n_chunks = T // chunk

    def r(x):  # (B,T,...) -> (N,B,C,...)
        return x.reshape((B, n_chunks, chunk) + x.shape[2:]).transpose(
            (1, 0, 2) + tuple(range(3, x.ndim + 1)))

    qs, ks, vs = r(q.astype(jnp.float32)), r(k.astype(jnp.float32)), \
        r(v.astype(jnp.float32))
    lis, lfs = r(log_i), r(log_f)
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(carry, inp):
        C0, n0, m0 = carry                       # (B,H,hd,hd),(B,H,hd),(B,H)
        qc, kc, vc, lic, lfc_raw = inp
        lfc = jnp.cumsum(lfc_raw, axis=1)        # (B,C,H) inclusive
        inter = lfc + m0[:, None]                # (B,C,H)
        logd = (lfc[:, :, None] - lfc[:, None, :] + lic[:, None, :])
        logd = jnp.where(tril[None, :, :, None], logd, -jnp.inf)
        m_intra = jnp.max(logd, axis=2)          # (B,C,H)
        m_t = jnp.maximum(inter, m_intra)
        dmat = jnp.exp(logd - m_t[:, :, None])
        sc = jnp.einsum("bthd,bshd->btsh", qc, kc)
        c = sc * dmat
        wi0 = jnp.exp(inter - m_t)               # (B,C,H)
        num = (jnp.einsum("btsh,bshd->bthd", c, vc)
               + wi0[..., None] * jnp.einsum("bhvk,bthk->bthv", C0, qc))
        n_t = (wi0[..., None] * n0[:, None]
               + jnp.einsum("btsh,bshd->bthd", dmat, kc))
        den = jnp.maximum(jnp.abs(jnp.einsum("bthd,bthd->bth", n_t, qc)),
                          jnp.exp(-m_t))
        h = num / den[..., None]
        # end-of-chunk state
        w_log = lfc[:, -1:, :] - lfc + lic       # (B,C,H)
        m_end = jnp.maximum(inter[:, -1], jnp.max(w_log, axis=1))
        w_end = jnp.exp(w_log - m_end[:, None])
        decay0 = jnp.exp(inter[:, -1] - m_end)   # (B,H)
        C1 = (decay0[..., None, None] * C0
              + jnp.einsum("bth,bthv,bthk->bhvk", w_end, vc, kc))
        n1 = decay0[..., None] * n0 + jnp.einsum("bth,bthk->bhk", w_end, kc)
        return (C1, n1, m_end), h

    init = (jnp.zeros((B, H, hd, hd), jnp.float32),
            jnp.zeros((B, H, hd), jnp.float32),
            jnp.full((B, H), -1e30, jnp.float32))
    (C1, n1, m1), hs = jax.lax.scan(step, init, (qs, ks, vs, lis, lfs))
    h = hs.transpose(1, 0, 2, 3, 4).reshape(B, T, H, hd)
    return h, {"C": C1, "n": n1, "m": m1}


def mlstm_block(p, x, n_heads, state=None, want_state=False, chunk=256):
    """xLSTM mLSTM: matrix memory.  Training: parallel stabilized form for
    short T, chunkwise-parallel for long T (O(T*chunk) memory).  Decode:
    recurrent form.  x: (B,T,D).  ``want_state`` additionally returns the
    final (C, n, m) (prefill)."""
    H = n_heads
    B, T, D = x.shape
    hd = D // H
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, T, H, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, T, H, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, T, H, hd)
    log_i = (x @ p["wi"].astype(x.dtype)).astype(jnp.float32)        # (B,T,H)
    log_f = jax.nn.log_sigmoid(
        (x @ p["wf"].astype(x.dtype)).astype(jnp.float32))           # (B,T,H)
    scale = 1.0 / jnp.sqrt(hd)

    if state is None and T > 2 * chunk and T % chunk == 0:
        kf = k.astype(jnp.float32) * scale
        h, new_state = _mlstm_chunkwise(q, kf, v, log_i, log_f, chunk)
        if not want_state:
            new_state = None
        h = h.reshape(B, T, D).astype(x.dtype)
        g = jax.nn.silu(x @ p["wg"].astype(x.dtype))
        return (h * g) @ p["wo"].astype(x.dtype), new_state

    if state is None:
        bcum = jnp.cumsum(log_f, axis=1)                             # (B,T,H)
        logd = (bcum[:, :, None] - bcum[:, None, :]
                + log_i[:, None, :])                                 # (B,t,s,H)
        tril = jnp.tril(jnp.ones((T, T), bool))
        logd = jnp.where(tril[None, :, :, None], logd, -jnp.inf)
        m = jnp.max(logd, axis=2, keepdims=True)                     # (B,t,1,H)
        dmat = jnp.exp(logd - m)
        s = jnp.einsum("bthd,bshd->btsh", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        c = s * dmat
        norm = jnp.maximum(jnp.abs(c.sum(axis=2)), jnp.exp(-m[:, :, 0]))
        h = jnp.einsum("btsh,bshd->bthd", c, v.astype(jnp.float32))
        h = h / norm[..., None]
        new_state = None  # training does not thread state
        if want_state:
            # final recurrent state from the parallel form (prefill):
            # C_T = sum_s exp(b_T - b_s + log i_s - m_T) v_s (k_s*scale)^T
            w_log = bcum[:, -1:, :] - bcum + log_i          # (B,T,H)
            m_T = jnp.max(w_log, axis=1)                    # (B,H)
            w = jnp.exp(w_log - m_T[:, None])               # (B,T,H)
            kf = k.astype(jnp.float32) * scale
            vf = v.astype(jnp.float32)
            C_T = jnp.einsum("bth,bthv,bthk->bhvk", w, vf, kf)
            n_T = jnp.einsum("bth,bthk->bhk", w, kf)
            new_state = {"C": C_T, "n": n_T, "m": m_T}
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]              # f32
        li, lf = log_i[:, 0], log_f[:, 0]                            # (B,H)
        m1 = jnp.maximum(lf + m0, li)
        fp = jnp.exp(lf + m0 - m1)[..., None, None]
        ip = jnp.exp(li - m1)[..., None, None]
        kf = k[:, 0].astype(jnp.float32) * scale
        vf = v[:, 0].astype(jnp.float32)
        C1 = fp * C0 + ip * (vf[..., :, None] * kf[..., None, :])    # (B,H,hd,hd)
        n1 = fp[..., 0] * n0 + ip[..., 0] * kf                       # (B,H,hd)
        qf = q[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhvk,bhk->bhv", C1, qf)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n1, qf)),
                          jnp.exp(-m1))
        h = (num / den[..., None])[:, None]                          # (B,1,H,hd)
        new_state = {"C": C1, "n": n1, "m": m1}
    h = h.reshape(B, T, D).astype(x.dtype)
    g = jax.nn.silu(x @ p["wg"].astype(x.dtype))
    return (h * g) @ p["wo"].astype(x.dtype), new_state


def mlstm_init_state(batch, d_model, n_heads, dtype):
    hd = d_model // n_heads
    return {"C": jnp.zeros((batch, n_heads, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, n_heads, hd), jnp.float32),
            "m": jnp.full((batch, n_heads), -1e30, jnp.float32)}


# ---------------------------------------------------------------- sLSTM

def init_slstm(key, d_model, n_heads, dtype):
    ks = jax.random.split(key, 3)
    d = d_model
    return {
        # gates i,f,z,o from x (fused) and recurrent block-diag from h
        "wx": trunc_normal(ks[0], (d, 4 * d), 1.0, dtype),
        "rh": trunc_normal(ks[1], (n_heads, d // n_heads, 4 * (d // n_heads)),
                           1.0, dtype),
        "wo": trunc_normal(ks[2], (d, d), 1.0, dtype),
    }


def slstm_block(p, x, n_heads, state=None):
    """True recurrence (gates see h_{t-1}); lax.scan over time.
    x: (B,T,D).  state: {'c','n','m','h'} each (B,D) f32."""
    H = n_heads
    B, T, D = x.shape
    hd = D // H
    gx = (x @ p["wx"].astype(x.dtype)).astype(jnp.float32)  # (B,T,4D)
    rh = p["rh"].astype(jnp.float32)                        # (H,hd,4hd)

    def step(carry, gxt):
        c, n, m, h = carry
        hh = h.reshape(B, H, hd)
        gr = jnp.einsum("bhk,hkg->bhg", hh, rh)          # (B,H,4*hd)
        # match gx layout [gate][head*hd]:
        gr = gr.reshape(B, H, 4, hd).transpose(0, 2, 1, 3).reshape(B, 4 * D)
        g = gxt + gr
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m1 = jnp.maximum(gf + m, gi)                        # exp. gating
        ip = jnp.exp(gi - m1)
        fp = jnp.exp(gf + m - m1)
        c1 = fp * c + ip * jnp.tanh(gz)
        n1 = fp * n + ip
        h1 = jax.nn.sigmoid(go) * c1 / jnp.maximum(n1, 1.0)
        return (c1, n1, m1, h1), h1

    if state is None:
        z = jnp.zeros((B, D), jnp.float32)
        carry = (z, z, jnp.full((B, D), -1e30, jnp.float32), z)
    else:
        carry = (state["c"], state["n"], state["m"], state["h"])
    carry, hs = jax.lax.scan(step, carry, gx.transpose(1, 0, 2))
    hs = hs.transpose(1, 0, 2).astype(x.dtype)              # (B,T,D)
    c, n, m, h = carry
    return hs @ p["wo"].astype(x.dtype), {"c": c, "n": n, "m": m, "h": h}


def slstm_init_state(batch, d_model):
    z = jnp.zeros((batch, d_model), jnp.float32)
    return {"c": z, "n": z, "m": jnp.full((batch, d_model), -1e30, jnp.float32),
            "h": z}
