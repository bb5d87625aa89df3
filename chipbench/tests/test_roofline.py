"""Bytes and least times on hand-worked count matrices."""
import numpy as np
import pytest

from chipbench import roofline, trace_reduce

PK = roofline.peaks("TPU v5 lite")


def test_peaks_table():
    assert PK["hbm_bytes_per_s"] == 819e9
    assert PK["ici_bits_per_s"] == 1600e9
    assert PK["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_interconnect_binds_without_own_rows():
    S = [[0, 10], [30, 0]]
    rows_in, rows_out = roofline.off_chip_rows(S)
    assert rows_in.tolist() == [30, 10] and rows_out.tolist() == [10, 30]
    least, bound = roofline.exchange_least_s(S, 1000, PK)
    assert bound == "ici"
    assert least == pytest.approx(30 * 1000 / 200e9)


def test_hbm_binds_with_a_large_own_block():
    S = [[100, 10], [30, 0]]
    least, bound = roofline.exchange_least_s(S, 1000, PK)
    assert bound == "hbm"
    assert least == pytest.approx((110 + 130) * 1000 / 819e9)


def test_slab_least_counts_off_chip_rows_of_the_rank():
    S = [[5, 1, 2], [3, 7, 4], [6, 8, 9]]
    # rank 1 sends 3 + 4 = 7 rows off-chip and receives 1 + 8 = 9
    assert roofline.slab_least_s(S, 1, 4096, PK) == pytest.approx(
        16 * 4096 / 819e9)


def test_share_is_none_without_device_time():
    assert roofline.share_pct(1.0, 0.0) is None
    assert roofline.share_pct(1.0, 4.0) == 25.0


@pytest.mark.parametrize("seed", range(20))
def test_share_never_passes_100_percent(seed):
    """A chip that moves the bytes at its peaks, whatever overlaps, is busy
    at least the least time: interconnect and HBM work may overlap fully,
    and the union of the two counts once, so the least time is the larger
    bound and never their sum."""
    rng = np.random.default_rng(seed)
    p = 4
    S = rng.integers(0, 5000, (p, p))
    rb = int(rng.choice([2048, 4096, 8192]))
    least, _ = roofline.exchange_least_s(S, rb, PK)
    rows_in, rows_out = roofline.off_chip_rows(S)
    device = {}
    for c in range(p):
        ici = max(rows_in[c], rows_out[c]) * rb / 200e9 * 1e9
        hbm = (S[c].sum() + S[:, c].sum()) * rb / 819e9 * 1e9
        slow = rng.uniform(1.0, 3.0)
        start = rng.uniform(0, 1e3)
        device[c] = [("collective-permute", start, start + ici * slow),
                     ("copy", start, start + hbm * slow)]
    red = trace_reduce.reduce(device, (0.0, 1e9))
    share = roofline.share_pct(least, max(red["busy_s"].values()))
    assert 0 < share <= 100.0 + 1e-9
