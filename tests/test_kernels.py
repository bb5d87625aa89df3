"""Per-kernel validation (deliverable c): interpret=True Pallas execution
vs the pure-jnp ref.py oracle, swept over shapes/dtypes + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ragged_gather.ops import (pack_blocks, ragged_gather,
                                             ragged_scatter, slab_extract,
                                             slab_merge, slab_step,
                                             unpack_blocks)
from repro.kernels.ragged_gather.ref import (pack_blocks_ref,
                                             ragged_gather_ref,
                                             ragged_scatter_ref,
                                             slab_extract_ref,
                                             slab_merge_ref, slab_step_ref)
from repro.kernels.rg_lru.ops import rglru_scan
from repro.kernels.rg_lru.ref import rglru_scan_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------ ragged gather

@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float16])
@pytest.mark.parametrize("n,f,m,br", [(64, 8, 128, 32), (300, 16, 500, 128),
                                      (128, 128, 128, 128)])
def test_ragged_gather_sweep(dtype, n, f, m, br):
    x = jnp.asarray(RNG.standard_normal((n, f)) * 10, dtype)
    idx = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    got = ragged_gather(x, idx, block_rows=br, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ragged_gather_ref(x, idx)))


@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=24),
       st.integers(min_value=1, max_value=7),
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_pack_blocks_property(n, cap, f, seed):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, cap + 1, n).astype(np.int32)
    blocks = rng.standard_normal((n, cap, f)).astype(np.float32)
    total_pad = int(sizes.sum()) + int(rng.integers(0, 8))
    total_pad = max(total_pad, 1)
    got = pack_blocks(jnp.asarray(blocks), jnp.asarray(sizes), total_pad,
                      block_rows=32, interpret=True)
    want = pack_blocks_ref(jnp.asarray(blocks), jnp.asarray(sizes), total_pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    # rank-order invariant: valid rows are the concatenation of blocks
    off = 0
    for i in range(n):
        np.testing.assert_allclose(np.asarray(got)[off: off + sizes[i]],
                                   blocks[i, : sizes[i]])
        off += sizes[i]


# ----------------------------------------------------------- ragged scatter

@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float16])
@pytest.mark.parametrize("n_out,f,m,br", [(64, 8, 32, 32), (300, 16, 96, 32),
                                          (128, 128, 128, 128)])
def test_ragged_scatter_sweep(dtype, n_out, f, m, br):
    """Unpack kernel vs jnp oracle over unique destinations (the dataplane
    case: unpack targets are injective by construction)."""
    rng = np.random.default_rng(n_out + m)
    x = jnp.asarray(rng.standard_normal((m, f)) * 10, dtype)
    idx = jnp.asarray(rng.permutation(n_out)[:m], jnp.int32)
    got = ragged_scatter(x, idx, n_out, block_rows=br, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ragged_scatter_ref(x, idx, n_out)))


def test_ragged_scatter_drops_out_of_range():
    x = jnp.ones((4, 3), jnp.float32)
    idx = jnp.asarray([0, 99, -1, 2], jnp.int32)
    got = np.asarray(ragged_scatter(x, idx, 8, block_rows=4, interpret=True))
    assert got[0].all() and got[2].all()
    assert not got[1].any() and not got[3:].any()  # dropped, buffer zero
    # ref shares the drop contract (kernel-vs-oracle differential holds
    # even with out-of-range destinations)
    np.testing.assert_array_equal(
        got, np.asarray(ragged_scatter_ref(x, idx, 8)))


@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=24),
       st.integers(min_value=1, max_value=7),
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_unpack_inverts_pack_property(n, cap, f, seed):
    """pack -> unpack round-trips every valid row, zero-size blocks
    included (the scatterv-side consolidation on TPU)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, cap + 1, n).astype(np.int32)
    sizes[rng.integers(0, n)] = 0  # always exercise a zero-size block
    blocks = rng.standard_normal((n, cap, f)).astype(np.float32)
    total_pad = max(1, int(sizes.sum()) + int(rng.integers(0, 8)))
    packed = pack_blocks(jnp.asarray(blocks), jnp.asarray(sizes), total_pad,
                         block_rows=32, interpret=True)
    unpacked = unpack_blocks(packed, jnp.asarray(sizes), cap,
                             block_rows=32, interpret=True)
    assert unpacked.shape == (n, cap, f)
    for i in range(n):
        np.testing.assert_array_equal(np.asarray(unpacked)[i, : sizes[i]],
                                      blocks[i, : sizes[i]])


# --------------------------------------------------------------- slab copies

@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_slab_ops_match_refs_property(rows, f, seed):
    rng = np.random.default_rng(seed)
    buf_rows = rows + int(rng.integers(0, 32))
    buf = jnp.asarray(rng.standard_normal((buf_rows, f)), jnp.float32)
    slab = jnp.asarray(rng.standard_normal((rows, f)), jnp.float32)
    start = int(rng.integers(0, buf_rows - rows + 1))
    valid = int(rng.integers(0, rows + 1))
    got_e = slab_extract(buf, start, rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_e),
                                  np.asarray(slab_extract_ref(buf, start,
                                                              rows)))
    got_m = slab_merge(buf, slab, start, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m),
                                  np.asarray(slab_merge_ref(buf, slab, start,
                                                            valid)))


def test_slab_ops_accept_traced_offsets():
    """The dataplane calls the slab kernels with traced per-device offsets
    (axis_index table lookups) — must trace and compile under jit."""
    buf = jnp.asarray(np.arange(40, dtype=np.float32).reshape(10, 4))
    slab = jnp.full((3, 4), -1.0, jnp.float32)

    @jax.jit
    def f(buf, s, v):
        return slab_merge(buf, slab, s, v, interpret=True)

    got = np.asarray(f(buf, jnp.int32(2), jnp.int32(2)))
    want = np.asarray(buf).copy()
    want[2:4] = -1.0
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------- fused step kernel

@given(st.integers(min_value=1, max_value=48),
       st.integers(min_value=1, max_value=48),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_slab_step_matches_merge_then_extract(rows_in, rows_out, f, seed):
    """The fused kernel == slab_merge followed by slab_extract, including
    the forwarding case where the outgoing slab overlaps the range that
    was just merged (the extract must see the merged rows)."""
    rng = np.random.default_rng(seed)
    buf_rows = max(rows_in, rows_out) + int(rng.integers(0, 32))
    buf = jnp.asarray(rng.standard_normal((buf_rows, f)), jnp.float32)
    got_slab = jnp.asarray(rng.standard_normal((rows_in, f)), jnp.float32)
    r0 = int(rng.integers(0, buf_rows - rows_in + 1))
    nv = int(rng.integers(0, rows_in + 1))
    s0 = int(rng.integers(0, buf_rows - rows_out + 1))
    new_buf, nxt = slab_step(buf, got_slab, r0, nv, s0, rows_out,
                             interpret=True)
    want_buf, want_nxt = slab_step_ref(buf, got_slab, r0, nv, s0, rows_out)
    np.testing.assert_array_equal(np.asarray(new_buf), np.asarray(want_buf))
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want_nxt))


def test_slab_step_extract_sees_merged_rows():
    """Forwarding regression pin: extract range == merge range — the
    returned slab must be the freshly received rows, not stale buffer."""
    buf = jnp.zeros((8, 2), jnp.float32)
    got = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    new_buf, nxt = slab_step(buf, got, 2, 4, 2, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(got))
    np.testing.assert_array_equal(np.asarray(new_buf)[2:6], np.asarray(got))


def test_slab_step_traced_offsets_under_jit():
    buf = jnp.asarray(np.arange(20, dtype=np.float32).reshape(10, 2))
    got = jnp.full((3, 2), -1.0, jnp.float32)

    @jax.jit
    def f(buf, r0, nv, s0):
        return slab_step(buf, got, r0, nv, s0, 3, interpret=True)

    new_buf, nxt = f(buf, jnp.int32(1), jnp.int32(2), jnp.int32(0))
    want = np.asarray(buf).copy()
    want[1:3] = -1.0
    np.testing.assert_array_equal(np.asarray(new_buf), want)
    np.testing.assert_array_equal(np.asarray(nxt), want[0:3])


# ------------------------------------------- row view at MoE widths (DMA)

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("op", ["slab_extract", "slab_merge", "slab_step",
                                "slab_merge_add", "slab_step_reduce"])
def test_slab_ops_row_view_match_refs(op, dtype):
    """The layout the executor hands the kernels: the (N, F/128, 128)
    row view at F=2048, odd row offsets, and slabs longer than one
    1 MiB fold tile (several tiles, the last one slid back), so the
    add kernels' tiling and masking run exactly as on the chip."""
    from repro.kernels.ragged_gather import ops, ref

    F, buf_rows, rows = 2048, 700, 301
    rng = np.random.default_rng(F + buf_rows)
    buf = jnp.asarray(rng.standard_normal((buf_rows, F)), dtype)
    slab = jnp.asarray(rng.standard_normal((rows, F)), dtype)
    start, valid, send = 37, 299, 211
    view = ops.row_view
    assert view(buf).shape == (buf_rows, 16, 128)
    args = {"slab_extract": (start, rows),
            "slab_merge": (start, valid),
            "slab_merge_add": (start, valid),
            "slab_step": (start, valid, send, 233),
            "slab_step_reduce": (start, valid, send, 233)}[op]
    pre = (buf,) if op == "slab_extract" else (buf, slab)
    got = getattr(ops, op)(*map(view, pre), *args, interpret=True)
    want = getattr(ref, op + "_ref")(*pre, *args)
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).reshape(w.shape),
                                      np.asarray(w))


@pytest.mark.parametrize("row,dtype", [((2, 128), jnp.float32),
                                       ((1, 96), jnp.float32),
                                       ((24, 128), jnp.bfloat16)])
@pytest.mark.parametrize("start", [0, 13, 23])   # 23 + 37 rows = buf_rows
def test_slab_fill_writes_only_its_input(row, dtype, start):
    """``slab_fill`` puts its input bit for bit at rows [start, start + n)
    and writes no other row: with uninitialised memory read as NaN,
    every other row is NaN."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.ragged_gather import ops

    n, buf_rows = 37, 60
    x = jnp.asarray(RNG.standard_normal((n,) + row), dtype)
    nan = pltpu.InterpretParams(uninitialized_memory="nan")
    got = np.asarray(jax.jit(lambda x, s: ops.slab_fill(
        x, buf_rows, s, interpret=nan))(x, jnp.int32(start)))
    assert got.shape == (buf_rows,) + row
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got[start:start + n].view(bits),
                                  np.asarray(x).view(bits))
    rest = np.delete(got, np.s_[start:start + n], axis=0)
    assert np.isnan(rest.astype(np.float32)).all()


# (row, dtype, chunk, src_start, dst_start, valid), in an alltoallv plan's
# bounds: every start + chunk within the 64-row buffer and the 40-row output
UNPACK_CASES = {
    "zero_row_block": ((2, 128), jnp.float32, 8, (3, 20, 41), (0, 4, 4),
                       (4, 0, 6)),
    "valid_is_chunk": ((2, 128), jnp.float32, 16, (0, 17, 48), (0, 16, 24),
                       (16, 8, 16)),
    "chunk_not_pow2": ((2, 128), jnp.float32, 13, (50, 1, 30), (0, 13, 24),
                       (13, 11, 5)),
    "adjacent_dst": ((1, 96), jnp.float32, 7, (9, 33, 2), (0, 5, 12),
                     (5, 7, 3)),
    "lanes_3072_of_2688": ((24, 128), jnp.bfloat16, 13, (7, 29, 51),
                           (0, 11, 24), (11, 13, 9)),
}


@pytest.mark.parametrize("case", sorted(UNPACK_CASES))
def test_slab_unpack_matches_ref(case):
    """``slab_unpack`` puts each block's valid rows bit for bit where
    ``ref.slab_unpack_ref`` puts them, and writes no other row: with
    uninitialised memory read as NaN, every row outside the blocks is
    NaN.  The last case is the 3072-lane row view that holds 2688-wide
    bf16 rows."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.ragged_gather import ops, ref

    row, dtype, chunk, *tables = UNPACK_CASES[case]
    buf_rows, out_rows = 64, 40
    buf = jnp.asarray(RNG.standard_normal((buf_rows,) + row), dtype)
    src, dst, valid = (jnp.asarray(t, jnp.int32) for t in tables)
    nan = pltpu.InterpretParams(uninitialized_memory="nan")
    got = np.asarray(jax.jit(lambda b, s, d, n: ops.slab_unpack(
        b, s, d, n, chunk, out_rows, interpret=nan))(buf, src, dst, valid))
    want = np.asarray(ref.slab_unpack_ref(buf, src, dst, valid, chunk,
                                          out_rows))
    assert got.shape == want.shape == (out_rows,) + row
    written = np.zeros(out_rows, bool)
    for d0, n in zip(tables[1], tables[2]):
        written[d0:d0 + n] = True
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got[written].view(bits),
                                  want[written].view(bits))
    assert np.isnan(got[~written].astype(np.float32)).all()


def test_row_view_layout():
    from repro.kernels.ragged_gather.ops import row_view

    assert row_view(jnp.zeros((5, 4096))).shape == (5, 32, 128)
    assert row_view(jnp.zeros((5, 12))).shape == (5, 1, 12)


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,hkv,t,hd,causal,window,bq,bk",
    [
        (2, 4, 2, 256, 64, True, None, 128, 128),
        (1, 4, 1, 256, 64, True, 128, 64, 64),    # MQA + sliding window
        (1, 2, 2, 384, 32, False, None, 128, 128),
        (1, 8, 2, 128, 128, True, None, 128, 128),  # GQA group 4
        (2, 2, 1, 512, 64, True, 256, 128, 128),
    ])
def test_flash_attention_sweep(dtype, b, h, hkv, t, hd, causal, window,
                               bq, bk):
    q = jnp.asarray(RNG.standard_normal((b, h, t, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, t, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, t, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(st.integers(min_value=1, max_value=3),
       st.sampled_from([1, 2, 4]),
       st.sampled_from([64, 128]),
       st.sampled_from([32, 64]),
       st.integers(min_value=0, max_value=1000))
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(b, g, t, hd, seed):
    rng = np.random.default_rng(seed)
    hkv = 2
    h = hkv * g
    q = jnp.asarray(rng.standard_normal((b, h, t, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, t, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, t, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------------- rg_lru

@pytest.mark.parametrize("B,T,D,bb,bd,ch", [(8, 512, 256, 8, 128, 128),
                                            (16, 256, 128, 8, 128, 64),
                                            (8, 1024, 384, 4, 128, 256)])
def test_rglru_scan_sweep(B, T, D, bb, bd, ch):
    a = jnp.asarray(RNG.uniform(0.5, 1.0, (B, T, D)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((B, T, D)) * 0.1, jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((B, D)), jnp.float32)
    h, hl = rglru_scan(a, b, h0, block_b=bb, block_d=bd, chunk=ch,
                       interpret=True)
    hr, hlr = rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(min_value=0, max_value=1000))
@settings(max_examples=10, deadline=None)
def test_rglru_scan_property(seed):
    rng = np.random.default_rng(seed)
    B, T, D = 8, 128, 128
    a = jnp.asarray(rng.uniform(0.0, 1.0, (B, T, D)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    h, hl = rglru_scan(a, b, h0, chunk=32, interpret=True)
    hr, hlr = rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-5, atol=1e-5)
