"""The ``kernel_ms`` readers on hand-made reductions and on a recorded
chip trace whose kernels carry no name."""
import gzip
import os
from types import SimpleNamespace

import pytest

from chipbench import run, trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ctx(ops_s: dict, busy_s: dict, draws: int):
    red = {"window_s": 2.0, "busy_s": busy_s, "ops_s": ops_s, "idle_s": {}}
    return SimpleNamespace(traces={"lib": {"reduction": red,
                                           "draws": list(range(draws))}})


@pytest.mark.parametrize("metric", ["kernel_ms.slab", "kernel_ms.exchange"])
def test_ms_per_unit_over_named_kernels(metric):
    ops = {"slab_extract/custom-call": 0.002, "slab_step/custom-call": 0.010,
           "slab_merge/custom-call": 0.004, "copy": 0.5,
           "run/custom-call": 0.3, "pad": 0.1}
    got = run.metric_reader(metric)(_ctx({0: ops}, {0: 1.8}, 8))
    assert got == pytest.approx(1e3 * 0.016 / 8)


def test_exchange_reads_the_busiest_chip():
    ops = {c: {"slab_step/custom-call": 0.001 * (c + 1), "copy": 0.2}
           for c in range(4)}
    busy = {0: 1.7, 1: 1.9, 2: 1.8, 3: 1.6}
    got = run.metric_reader("kernel_ms.exchange")(_ctx(ops, busy, 4))
    assert got == pytest.approx(1e3 * 0.002 / 4)


@pytest.mark.parametrize("metric", ["kernel_ms.slab", "kernel_ms.exchange"])
def test_nothing_to_read(metric):
    read = run.metric_reader(metric)
    assert read(SimpleNamespace(traces={})) is None
    assert read(_ctx({0: {"copy": 0.5}}, {0: 0.5}, 4)) is None


def test_anonymous_kernels_of_a_recorded_trace_read_none(tmp_path):
    """Slab passes traced on one v5e chip before the kernels had names:
    the one kernel entry is ``run/custom-call``, which no reader
    counts."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "mixtral-slab-v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    device, host = tr.read_xplane(str(path), ("chipbench_segment",))
    (seg,) = [(s, e) for n, s, e in host if n == "chipbench_segment"]
    red = tr.reduce(device, seg)
    assert "run/custom-call" in red["ops_s"][0]
    ctx = SimpleNamespace(traces={"lib": {"reduction": red,
                                          "draws": list(range(84))}})
    assert run.metric_reader("kernel_ms.slab")(ctx) is None
