"""Each driver end to end at a tiny size on four virtual CPU devices, the
slab kernels interpreted: a run is correct, its control is not, and
every cell, driver and metric resolves from its files."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import roofline, run

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
SEEDS = (2 ** 31 + 11, 5)


def tiny(cell: str) -> dict:
    spec = run.load_cell(cell)
    spec["config"] = dict(spec["config"], hidden_size=256)
    spec["traffic"] = dict(spec["traffic"], tokens_per_chip=16)
    return spec


@pytest.fixture(scope="module")
def devices():
    run.prepare_program("interpret")
    import jax

    return jax.devices()


def quiet(*_a, **_k):
    pass


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    spec = run.load_cell(cell)
    mod = run.driver_module(spec["traffic"]["driver"])
    names = {m["name"] for m in spec["end_to_end"]}
    assert names == set(mod.E2E) | {"setup_s"}
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
        assert m["moves"] in names


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_run_is_correct(cell, seed, devices):
    spec = tiny(cell)
    out = run.run_cell(spec, devices[: spec["cell"]["chips"]], seed, 0.3,
                       False, log=quiet)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_control_is_not_correct(cell, devices):
    spec = tiny(cell)
    out = run.run_cell(spec, devices[: spec["cell"]["chips"]], 3, 0.2,
                       False, control="float8_e4m3fn", log=quiet)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_same_seed_same_inputs(devices):
    from chipbench import routing

    spec = tiny("mixtral-8x7b-ep4.train")
    a = routing.routing_pool(spec["config"], spec["traffic"])
    b = routing.routing_pool(spec["config"], spec["traffic"])
    assert all((x == y).all() for x, y in zip(a, b))
    assert (routing.cycle_order(4, 2 ** 40 + 3)
            == routing.cycle_order(4, 2 ** 40 + 3)).all()
    import jax.numpy as jnp

    k1, k2 = routing.payload_key(2 ** 33 + 1), routing.payload_key(1)
    x1 = routing.payload(k1, (8, 128), jnp.bfloat16)
    assert (routing.as_bits(x1) == routing.as_bits(
        routing.payload(routing.payload_key(2 ** 33 + 1), (8, 128),
                        jnp.bfloat16))).all()
    assert not (routing.as_bits(x1) == routing.as_bits(
        routing.payload(k2, (8, 128), jnp.bfloat16))).all()
    assert bool(jnp.isfinite(x1.astype(jnp.float32)).all())


def test_metric_readers_on_a_synthetic_trace():
    S = np.array([[0, 100, 50, 20], [10, 0, 30, 40], [70, 10, 0, 5],
                  [1, 2, 3, 0]])
    plan = SimpleNamespace(tree_bytes_padded=120, tree_bytes_exact=100)
    red = {"window_s": 1.0, "busy_s": {0: 0.5, 1: 0.7},
           "ops_s": {}, "idle_s": {}}
    ctx = SimpleNamespace(
        layer={"row_bytes": 8192, "pool": [S], "plans": [(plan, plan)],
               "ran": [0, 0], "plan_s": [1e-4, 3e-4], "ranks": [1]},
        traces={"lib": {"reduction": red, "draws": [0, 0]},
                "xla": {"reduction": dict(red, busy_s={0: 0.1, 1: 0.2}),
                        "draws": [0, 0]}},
        peaks=roofline.peaks("TPU v5 lite"))
    read = {m["name"]: run.metric_reader(m["name"])(ctx)
            for m in BENCH["per_layer"]}
    assert read["plan_us.exchange"] == pytest.approx(200.0)
    assert read["pad_ratio.exchange"] == pytest.approx(1.2)
    assert read["xla_ratio.exchange"] == pytest.approx(3.5)
    assert read["idle_share.exchange"] == pytest.approx(0.4)
    assert 0 < read["exchange_roofline"] < 100
    assert 0 < read["slab_roofline"] < 100
    empty = SimpleNamespace(layer={}, traces={}, peaks=None)
    assert all(run.metric_reader(m["name"])(empty) is None
               for m in BENCH["per_layer"])
