"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

Per chip: the union of the intervals in which an operation ran on the
device (busy), its complement inside the traced window (idle gaps), and
the summed device duration of each operation.  Idle gaps are attributed
to what the host was doing, read from the host's
``jax.profiler.TraceAnnotation`` spans.

Everything below :func:`read_xplane` works on plain ``(name, start_ns,
end_ns)`` tuples, so it is checked without a trace file.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote under
    ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def read_xplane(path: str, host_names=()) -> tuple[dict, list]:
    """``({chip: [(op, start_ns, end_ns), ...]}, [(span, start_ns,
    end_ns), ...])``: the device operations of each TPU and the host spans
    whose name is in ``host_names``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: dict[int, list] = {}
    host: list = []
    wanted = set(host_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            device[int(m.group(1))] = ops
        elif plane.name == HOST_PLANE and wanted:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in wanted)
    return device, host


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` around disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


_HLO = re.compile(r"%?(\S+) = .*? ([a-z][\w-]*)\(")


def op_key(name: str) -> str:
    """An operation's name without XLA's numeric suffix (``fusion.12`` and
    ``fusion.3`` are both ``fusion``).  A TPU trace names each operation
    by its HLO text; that becomes the instruction's name, with its opcode
    where the two differ (``copy_bitcast_fusion/fusion``)."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"(\.\d+)+$", "", name)
    inst = re.sub(r"(\.\d+)+$", "", m.group(1))
    return inst if inst == m.group(2) else f"{inst}/{m.group(2)}"


def host_activity(gap, host) -> str:
    """The host span that overlaps ``gap`` most, or ``"none"``."""
    best, best_overlap = "none", 0.0
    for name, s, e in host:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(device: dict, window: tuple[float, float],
           host=()) -> dict:
    """Busy, idle and per-operation seconds of each chip inside ``window``
    (``(lo_ns, hi_ns)``).

    Returns ``{"window_s", "busy_s": {chip: s}, "ops_s": {chip: {op: s}},
    "idle_s": {chip: {host activity: s}}}``.  An operation counts for the
    part of it that lies inside the window; operations that overlap count
    once in ``busy_s`` and each in ``ops_s``.
    """
    lo, hi = window
    out = {"window_s": (hi - lo) * 1e-9, "busy_s": {}, "ops_s": {},
           "idle_s": {}}
    for chip, ops in sorted(device.items()):
        spans = clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(spans)
        out["busy_s"][chip] = sum(e - s for s, e in busy) * 1e-9
        per_op: dict[str, float] = defaultdict(float)
        for name, s, e in ops:
            for cs, ce in clip([(s, e)], lo, hi):
                per_op[op_key(name)] += (ce - cs) * 1e-9
        out["ops_s"][chip] = dict(per_op)
        idle: dict[str, float] = defaultdict(float)
        for g in gaps(busy, lo, hi):
            idle[host_activity(g, host)] += (g[1] - g[0]) * 1e-9
        out["idle_s"][chip] = dict(idle)
    return out


def mean_over_chips(per_chip: dict) -> float:
    return sum(per_chip.values()) / len(per_chip) if per_chip else 0.0


def top(per_chip: dict, n: int = 10) -> list[list]:
    """The ``n`` largest entries of ``{chip: {name: s}}``, as the mean over
    chips, largest first."""
    total: dict[str, float] = defaultdict(float)
    for entries in per_chip.values():
        for name, s in entries.items():
            total[name] += s / len(per_chip)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]
