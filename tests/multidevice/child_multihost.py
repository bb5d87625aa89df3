"""Multi-process (emulated multi-host) conformance lane.

ONE process of a ``jax.distributed`` CPU job: the pytest wrapper
(``tests/test_multihost.py``) launches ``NUM_PROCESSES`` copies of this
script, each forcing 4 host-platform devices, so the job forms a real
2-host x 4-device mesh with gloo cross-process collectives — the closest
thing to multi-host hardware a CI box can offer.  Every process runs the
same SPMD programs and independently asserts:

* gatherv / scatterv / allgatherv / alltoallv — flat TUW plans AND the
  hierarchical two-level schedules — produce byte-identical results to
  the single-host NumPy oracle on its addressable shards;
* ``HostTopology.from_mesh`` sees 2x4 via ``device.process_index`` and
  ``mesh_fingerprint`` embeds it (so multi-host plans never collide with
  single-host ones in the cache);
* a plan-only ``PlannerService`` over the live mesh keys and selects
  with the inferred topology.

Usage (normally via the pytest wrapper):

    python child_multihost.py <process_id> <num_processes> <port>
"""
import os
import sys

PROCESS_ID = int(sys.argv[1])
NUM_PROCESSES = int(sys.argv[2])
PORT = sys.argv[3]
DEVICES_PER_PROCESS = 4

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={DEVICES_PER_PROCESS}")

import jax  # noqa: E402

try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{PORT}",
        num_processes=NUM_PROCESSES, process_id=PROCESS_ID)
except Exception as e:  # pragma: no cover - environment-dependent
    print(f"MULTIHOST-SKIP: jax.distributed unavailable: {e}", flush=True)
    sys.exit(0)

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import jax_collectives as jc  # noqa: E402
from repro.core.baselines import two_level_tree  # noqa: E402
from repro.core.composed import alltoallv_schedule  # noqa: E402
from repro.core.costmodel import (CostParams, HierarchicalCostParams,  # noqa: E402
                                  HostTopology)
from repro.tuner import PlannerService, mesh_fingerprint  # noqa: E402

jc.set_dataplane("xla")  # CPU devices: the jnp slab reference

AXIS = ("host", "device")  # tuple axis: flattened host-major by JAX
PP = NUM_PROCESSES * DEVICES_PER_PROCESS


def hier_mesh():
    devs = np.array(jax.devices()).reshape(NUM_PROCESSES, DEVICES_PER_PROCESS)
    return Mesh(devs, ("host", "device"))


def global_array(mesh, full: np.ndarray):
    """Shard a (deterministically identical on every process) host array
    over the flattened (host, device) axis."""
    sh = NamedSharding(mesh, P(AXIS))
    return jax.make_array_from_callback(full.shape, sh, lambda idx: full[idx])


def run_body(mesh, body, full_in: np.ndarray):
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False))
    out = fn(global_array(mesh, full_in))
    rows = out.shape[0] // PP
    shards = {}
    for s in out.addressable_shards:
        dev = s.index[0].start // rows if s.index[0].start else 0
        shards[dev] = np.asarray(s.data)
    return shards, rows


def check_rows(shards, device, lo, hi, want, ctx):
    """Assert rows [lo:hi) of ``device``'s shard equal ``want`` — only on
    the process that owns the device."""
    if device in shards:
        np.testing.assert_array_equal(shards[device][lo:hi], want,
                                      err_msg=ctx)


def check_topology(mesh):
    topo = HostTopology.from_mesh(mesh)
    assert (topo.hosts, topo.devices_per_host) == (NUM_PROCESSES,
                                                   DEVICES_PER_PROCESS), topo
    fp = mesh_fingerprint(mesh)
    assert f"hosts={NUM_PROCESSES}x{DEVICES_PER_PROCESS}" in fp, fp
    assert mesh_fingerprint(mesh) != mesh_fingerprint(
        mesh, HostTopology(1, PP))
    print(f"[{PROCESS_ID}] topology OK: {fp}", flush=True)
    return topo


def check_rooted(mesh, topo, tree_name, tree_of):
    rng = np.random.default_rng(7)
    sizes = [int(s) for s in rng.integers(0, 30, PP)]
    sizes[3] = 0  # zero block stays legal across the host boundary
    root = 5
    F = 3
    blocks = [rng.standard_normal((s, F)).astype(np.float32) for s in sizes]
    live = [b for b in blocks if len(b)]
    truth = np.concatenate(live, axis=0) if live else np.zeros((0, F),
                                                               np.float32)
    plan = jc.plan_gatherv(sizes, root, tree=tree_of(sizes, root))
    x = np.zeros((PP, plan.cap, F), np.float32)
    for i, b in enumerate(blocks):
        x[i, : sizes[i]] = b
    shards, rows = run_body(
        mesh, lambda xl: jc.gatherv_shard(xl, plan, AXIS),
        x.reshape(PP * plan.cap, F))
    check_rows(shards, root, 0, plan.total, truth,
               f"{tree_name} gatherv root buffer")
    # scatterv: reverse walk over the same plan
    xin = np.zeros((PP, plan.buf_rows, F), np.float32)
    xin[root, : plan.total] = truth
    shards, rows = run_body(
        mesh, lambda xl: jc.scatterv_shard(xl, plan, AXIS),
        xin.reshape(PP * plan.buf_rows, F))
    for i in range(PP):
        check_rows(shards, i, 0, sizes[i], blocks[i],
                   f"{tree_name} scatterv block {i}")
    print(f"[{PROCESS_ID}] {tree_name} gatherv/scatterv OK "
          f"(p={PP}, root={root})", flush=True)


def check_allgatherv(mesh, topo, tree_name, tree_of):
    rng = np.random.default_rng(11)
    sizes = [int(s) for s in rng.integers(1, 25, PP)]
    root = 0
    F = 2
    blocks = [rng.standard_normal((s, F)).astype(np.float32) for s in sizes]
    truth = np.concatenate(blocks, axis=0)
    from repro.core.composed import allgatherv_schedule

    sched = allgatherv_schedule(sizes, root=root,
                                tree=tree_of(sizes, root))
    plan = jc.plan_allgatherv(sizes, root=root, schedule=sched)
    x = np.zeros((PP, plan.cap, F), np.float32)
    for i, b in enumerate(blocks):
        x[i, : sizes[i]] = b
    shards, rows = run_body(
        mesh, lambda xl: jc.allgatherv_shard(xl, plan, AXIS),
        x.reshape(PP * plan.cap, F))
    for j in range(PP):
        check_rows(shards, j, 0, plan.total, truth,
                   f"{tree_name} allgatherv device {j}")
    print(f"[{PROCESS_ID}] {tree_name} allgatherv OK", flush=True)


def check_alltoallv(mesh, topo, tree_name, schedule_of):
    rng = np.random.default_rng(13)
    S = rng.integers(0, 9, (PP, PP))
    F = 2
    ab = [[rng.standard_normal((int(S[i, j]), F)).astype(np.float32)
           for j in range(PP)] for i in range(PP)]
    plan = jc.plan_alltoallv(S, schedule=schedule_of(S))
    x = np.zeros((PP, plan.cap, F), np.float32)
    for i, row in enumerate(ab):
        off = 0
        for b in row:
            x[i, off: off + len(b)] = b
            off += len(b)
    shards, rows = run_body(
        mesh, lambda xl: jc.alltoallv_shard(xl, plan, AXIS),
        x.reshape(PP * plan.cap, F))
    for j in range(PP):
        want = np.concatenate([ab[i][j] for i in range(PP)], axis=0)
        check_rows(shards, j, 0, plan.out_valid[j], want,
                   f"{tree_name} alltoallv device {j}")
    print(f"[{PROCESS_ID}] {tree_name} alltoallv OK", flush=True)


def check_planner_service(mesh, topo):
    """Planning over the live multi-process mesh: topology-inferred keys,
    hierarchical params, a two-level selection on the decode regime."""
    ici = CostParams(1e-6, 2e-11, "s", "byte")
    hp = HierarchicalCostParams(
        ici, CostParams(50e-6, 16e-11, "s", "byte"), topo)
    svc = PlannerService(mesh=mesh, quantum=16, params=hp,
                         segments=(1, 2), wave_bins=(2.0,))
    assert (svc.topology.hosts, svc.topology.devices_per_host) == \
        (NUM_PROCESSES, DEVICES_PER_PROCESS)
    key = svc._key("gatherv", [64] * PP, 0, "float32", 4)
    assert f"hosts={NUM_PROCESSES}x{DEVICES_PER_PROCESS}" in key.mesh
    rng = np.random.default_rng(3)
    loads = rng.dirichlet(np.full(PP, 0.3))
    S = (np.outer(np.full(PP, 1.0 / PP), loads) * PP * 192).astype(np.int64)
    rec = svc.plan_record("alltoallv", S, row_bytes=4096)
    names = [n for n, _ in rec.costs]
    assert any(n.startswith("two_level") for n in names), names
    print(f"[{PROCESS_ID}] planner service OK: selected {rec.algo} "
          f"among {len(names)} candidates", flush=True)


def main():
    assert jax.process_count() == NUM_PROCESSES, jax.process_count()
    assert jax.device_count() == PP, jax.devices()
    mesh = hier_mesh()
    topo = check_topology(mesh)
    D = topo.devices_per_host
    flat = lambda m, r: None  # None => the default TUW construction
    two_level = lambda m, r: two_level_tree(m, r, D)
    check_rooted(mesh, topo, "tuw", flat)
    check_rooted(mesh, topo, "two_level", two_level)
    check_allgatherv(mesh, topo, "tuw", flat)
    check_allgatherv(mesh, topo, "two_level", two_level)
    check_alltoallv(mesh, topo, "tuw", alltoallv_schedule)
    check_alltoallv(
        mesh, topo, "two_level",
        lambda S: alltoallv_schedule(
            S, tree_builder=lambda row, r: two_level_tree(row, r, D)))
    check_planner_service(mesh, topo)
    print(f"[{PROCESS_ID}] ALL MULTIHOST CHECKS PASSED", flush=True)


if __name__ == "__main__":
    main()
