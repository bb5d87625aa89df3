"""``lane_pad.exchange`` on four virtual CPU devices, the slab kernels
interpreted: a few tokens per chip at each exchange cell's published row
width, read from the program's own count after set-up."""
from types import SimpleNamespace

import pytest

from chipbench import run
from chipbench.drivers import exchange


@pytest.fixture(scope="module")
def devices():
    run.prepare_program("interpret")
    import jax

    return jax.devices()


@pytest.mark.parametrize("cell, ratio", [
    ("mixtral-8x7b-ep4.train", 1.0),                     # 4096: whole tiles
    ("nemotron-3-nano-30b-a3b-ep4.train", 3072 / 2688),  # 21 lane groups
])
def test_lane_pad_reads_moved_over_published_row_bytes(cell, ratio, devices):
    spec = run.load_cell(cell)
    traffic = dict(spec["traffic"], tokens_per_chip=16)
    drv = exchange.Driver(spec["config"], traffic, devices[:4], 7,
                          trace=False)
    ctx = SimpleNamespace(layer=drv.layer_context(), traces={}, peaks=None)
    assert run.metric_reader("lane_pad.exchange")(ctx) == pytest.approx(
        ratio, rel=1e-12)
    drv.free()
    verdict = drv.check()
    assert all(v == 0 for v, _ in verdict["checks"].values()), verdict
