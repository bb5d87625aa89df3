"""The trace reduction on hand-made events and on a recorded chip trace."""
import gzip
import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tr.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_counts_overlap_once_and_attributes_gaps():
    device = {0: [("fusion.1", 0, 40), ("copy.2", 30, 60),
                  ("fusion.7", 80, 100)],
              1: [("collective-permute-done.3", 10, 90)]}
    host = [("plan", 55, 75), ("wait", 75, 100)]
    red = tr.reduce(device, (0, 100), host)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"][0] == pytest.approx(80e-9)
    assert red["busy_s"][1] == pytest.approx(80e-9)
    assert red["ops_s"][0] == pytest.approx({"fusion": 60e-9,
                                             "copy": 30e-9})
    assert red["idle_s"][0] == pytest.approx({"plan": 20e-9})
    assert red["idle_s"][1] == pytest.approx({"none": 10e-9,
                                              "wait": 10e-9})
    assert tr.mean_over_chips(red["busy_s"]) == pytest.approx(80e-9)
    top = tr.top(red["ops_s"])
    assert top[0][0] == "collective-permute-done"
    assert top[0][1] == pytest.approx(40e-9)


def test_reduce_clips_to_the_window():
    red = tr.reduce({0: [("a", 0, 50), ("b", 90, 200)]}, (25, 100))
    assert red["busy_s"][0] == pytest.approx(35e-9)
    assert red["ops_s"][0] == pytest.approx({"a": 25e-9, "b": 10e-9})


def test_op_key():
    assert tr.op_key("fusion.12") == "fusion"
    assert tr.op_key("copy-start.1.2") == "copy-start"
    assert tr.op_key("slab_step") == "slab_step"
    assert tr.op_key("%copy.7 = bf16[12116,8,32,128]{3,1,2,0:T(8,128)(2,1)}"
                     " copy(bf16[12116,8,32,128]{3,2,1,0:T(8,128)(2,1)} "
                     "%bitcast.3)") == "copy"
    assert tr.op_key("%copy_bitcast_fusion = bf16[81920,4096]{1,0} fusion("
                     "bf16[10240,8,32,128]{3,2,1,0} %bitcast.2), kind=kLoop"
                     ) == "copy_bitcast_fusion/fusion"
    assert tr.op_key("%run.12 = (bf16[81920,32,128]{2,1,0:T(8,128)(2,1)}, "
                     "bf16[8192,32,128]{2,1,0}) custom-call(s32[1]{0} "
                     "%dynamic_slice.31)") == "run/custom-call"


def test_recorded_chip_trace(tmp_path):
    """Slab passes of the mixtral cell traced on one v5e chip for two
    seconds: the reduction finds the chip, its operations and the host
    spans on one clock."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "mixtral-slab-v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    device, host = tr.read_xplane(str(path), ("chipbench_segment", "launch",
                                              "wait"))
    assert list(device) == [0]
    seg = [(s, e) for n, s, e in host if n == "chipbench_segment"]
    assert len(seg) == 1
    red = tr.reduce(device, seg[0], [h for h in host if h[0] != "chipbench_segment"])
    assert 0 < red["busy_s"][0] <= red["window_s"]
    assert sum(red["ops_s"][0].values()) >= red["busy_s"][0] * (1 - 1e-9)
    idle = red["window_s"] - red["busy_s"][0]
    assert sum(red["idle_s"][0].values()) == pytest.approx(idle)
    assert 1.8 < red["busy_s"][0] < red["window_s"] < 2.1
    top = dict(tr.top(red["ops_s"]))
    assert {"copy", "slice", "pad", "copy_bitcast_fusion/fusion",
            "run/custom-call"} <= set(top)
