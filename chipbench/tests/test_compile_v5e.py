"""Each cell's programs at full size, compiled for a described v5e 2x2
(no chip needed): the slab kernels are in them and each program fits one
chip's memory."""
import json
import os

import pytest

from chipbench import routing, run

CELLS = [w["name"] for w in json.load(open(os.path.join(
    run.ROOT, "BENCHMARK.json")))["workloads"]]
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    run.prepare_program("pallas")
    yield


@pytest.mark.parametrize("cell", CELLS)
def test_cell_compiles_for_v5e(cell, topo, no_cache):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    spec = run.load_cell(cell)
    cfg, traffic = spec["config"], spec["traffic"]
    pool = routing.routing_pool(cfg, traffic)
    dtype = jnp.dtype(cfg["dtype"])
    F = cfg["hidden_size"]
    if traffic["driver"] == "exchange":
        from chipbench.drivers import exchange

        mesh = jax.make_mesh((4,), ("x",), devices=topo.devices[:4],
                             axis_types=(AxisType.Auto,))
        _, progs, _ = exchange.compile_programs(mesh, pool, dtype, F)
        progs = list(progs.values())
    else:
        from chipbench.drivers import slab

        ranks = [int(np.argmax(S.sum(axis=0))) for S in pool]
        _, pairs = slab.compile_programs(pool, ranks, dtype, F,
                                         topo.devices[0])
        progs = [p for pair in pairs for p in pair]
    assert len(progs) == 2 * len(pool)
    for prog in progs:
        assert "tpu_custom_call" in prog.as_text()
        mem = prog.memory_analysis()
        per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert per_chip <= HBM, f"{cell}: {per_chip} bytes per chip"
