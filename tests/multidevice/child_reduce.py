"""Multi-device checks for the REDUCTION collectives (reduce_scatterv /
allreducev).  Run in a SUBPROCESS (never under the main pytest process) so
the 8 fake host devices don't leak into other tests:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python child_reduce.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
from jax.sharding import AxisType

from repro.core.composed import (
    reduce_scatterv_direct_schedule, reduce_scatterv_halving_schedule,
)
from repro.core.distributions import NAMES, block_sizes
from repro.core.jax_collectives import (plan_allreducev, plan_reduce_scatterv,
                                        run_plan, set_dataplane)

PP = 8


def mesh1d():
    return jax.make_mesh((PP,), ("x",), axis_types=(AxisType.Auto,))


def _contribs(rng, total, F=3):
    return [rng.standard_normal((total, F)).astype(np.float32)
            for _ in range(PP)]


def run_rs(mesh, contribs, sizes, **kw):
    """reduce_scatterv of ``contribs`` under ``plan_reduce_scatterv(sizes,
    **kw)``."""
    return run_plan("reduce_scatterv", plan_reduce_scatterv(sizes, **kw),
                    mesh, "x", contribs, sizes)


def check_reduce_scatterv_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(0)
    for name in NAMES:
        sizes = block_sizes(name, PP, 9, seed=5)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        contribs = _contribs(rng, int(offs[-1]))
        outs, plan = run_rs(mesh, contribs, sizes)
        want = np.sum(contribs, axis=0)
        for j in range(PP):
            np.testing.assert_allclose(
                outs[j], want[offs[j]: offs[j] + sizes[j]],
                rtol=0, atol=1e-5)
    print("reduce_scatterv oracle OK (all shapes)")


def check_schedule_variants_agree():
    mesh = mesh1d()
    rng = np.random.default_rng(1)
    sizes = [7, 0, 3, 12, 1, 0, 5, 9]
    total = int(np.sum(sizes))
    contribs = _contribs(rng, total)
    tuw, _ = run_rs(mesh, contribs, sizes)
    direct, _ = run_rs(mesh, contribs, sizes,
                       schedule=reduce_scatterv_direct_schedule(sizes))
    halving, _ = run_rs(mesh, contribs, sizes,
                        schedule=reduce_scatterv_halving_schedule(sizes))
    for a, b, c in zip(tuw, direct, halving):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
        np.testing.assert_allclose(a, c, rtol=0, atol=1e-5)
    print("reduce_scatterv schedule variants agree (tuw/direct/halving)")


def check_bitwise_repeatable():
    mesh = mesh1d()
    rng = np.random.default_rng(2)
    sizes = block_sizes("spikes", PP, 11, seed=3)
    contribs = _contribs(rng, int(np.sum(sizes)))
    a, _ = run_rs(mesh, contribs, sizes)
    b, _ = run_rs(mesh, contribs, sizes)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)  # BITWISE, not approx
    # pipelined run is bitwise-identical to the monolithic one: the fold
    # order per flat row is the same step order either way
    c, _ = run_rs(mesh, contribs, sizes, segments=2)
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)
    print("reduce_scatterv bitwise repeatable (rerun + pipelined)")


def check_allreducev_oracle():
    mesh = mesh1d()
    rng = np.random.default_rng(4)
    sizes = block_sizes("decreasing", PP, 6, seed=7)
    contribs = _contribs(rng, int(np.sum(sizes)))
    out, plan = run_plan("allreducev", plan_allreducev(sizes), mesh, "x",
                         contribs, sizes)
    want = np.sum(contribs, axis=0)
    for j in range(PP):  # EVERY device holds the full reduced vector
        np.testing.assert_allclose(out[j], want, rtol=0, atol=1e-5)
    for j in range(1, PP):  # and all copies are bitwise identical
        np.testing.assert_array_equal(out[0], out[j])
    print("allreducev oracle OK (all devices, identical copies)")


def check_service_execution():
    from repro.tuner import PlannerService

    mesh = mesh1d()
    rng = np.random.default_rng(5)
    svc = PlannerService(mesh=mesh, axis_name="x", quantum=4)
    sizes = [5, 9, 0, 2, 13, 1, 6, 4]
    total = int(np.sum(sizes))
    offs = np.concatenate([[0], np.cumsum(sizes)])
    contribs = _contribs(rng, total, F=2)
    want = np.sum(contribs, axis=0)
    outs, plan = svc.reduce_scatterv(contribs, sizes)
    for j in range(PP):
        np.testing.assert_allclose(
            outs[j], want[offs[j]: offs[j] + sizes[j]], rtol=0, atol=1e-5)
    full, _ = svc.allreducev(contribs, sizes)
    for j in range(PP):
        np.testing.assert_allclose(full[j], want, rtol=0, atol=1e-5)
    # the quantized plan is cached: same signature, same record
    outs2, plan2 = svc.reduce_scatterv(contribs, sizes)
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)
    print("planner-service reduce execution OK (quantized + cached)")


if __name__ == "__main__":
    assert jax.device_count() == PP, jax.devices()
    set_dataplane("xla")  # CPU devices: the jnp slab reference
    check_reduce_scatterv_oracle()
    check_schedule_variants_agree()
    check_bitwise_repeatable()
    check_allreducev_oracle()
    check_service_execution()
    print("ALL REDUCE MULTIDEVICE CHECKS PASSED")
