"""Bytes and least times, computed from the count matrix alone.

A least time reads the same work whatever implements the exchange, so a
later executor is measured against the same bound.  All counts are in
rows of ``row_bytes``; ``S[i][j]`` is the number of rows chip ``i`` sends
to chip ``j``.
"""
from __future__ import annotations

import json
import os

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a device not in the table is
    an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def off_chip_rows(S) -> tuple[np.ndarray, np.ndarray]:
    """Rows each chip receives from, and sends to, other chips."""
    S = np.asarray(S, np.int64)
    own = np.diag(S)
    return S.sum(axis=0) - own, S.sum(axis=1) - own


def exchange_least_s(S, row_bytes: int, pk: dict) -> tuple[float, str]:
    """Least time of one ``alltoallv(S)`` and the bound that sets it.

    ``ici``: the chip that moves most off-chip bytes in either direction,
    at the chip's interconnect peak.  ``hbm``: the chip that must read
    the rows it sends (its own block included, which has to land in the
    output) and write the rows it receives, at the HBM peak.
    """
    S = np.asarray(S, np.int64)
    rows_in, rows_out = off_chip_rows(S)
    ici = float(np.maximum(rows_in, rows_out).max()) * row_bytes / (
        pk["ici_bits_per_s"] / 8)
    hbm = float((S.sum(axis=0) + S.sum(axis=1)).max()) * row_bytes / (
        pk["hbm_bytes_per_s"])
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")


def slab_least_s(S, rank: int, row_bytes: int, pk: dict) -> float:
    """HBM least time of rank ``rank``'s slab data plane for
    ``alltoallv(S)``: read each row it sends off-chip once and write each
    row it receives from another chip once."""
    rows_in, rows_out = off_chip_rows(S)
    return float(rows_in[rank] + rows_out[rank]) * row_bytes / (
        pk["hbm_bytes_per_s"])


def share_pct(least_s: float, device_s: float) -> float | None:
    """``least_s`` as a percentage of ``device_s``; None where no device
    time was read."""
    if not device_s > 0:
        return None
    return 100.0 * least_s / device_s
