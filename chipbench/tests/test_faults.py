"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false for each fault the cell can have.  The harness's
look for a chip is skipped; the slab kernels are interpreted at a tiny
size on four virtual CPU devices."""
import pytest

from chipbench import run
from chipbench.drivers import slab
from test_rehearsal import quiet, tiny


@pytest.fixture
def jc():
    run.prepare_program("interpret")
    from repro.core import jax_collectives

    return jax_collectives


def run_tiny(cell):
    import jax

    spec = tiny(cell)
    return run.run_cell(spec, jax.devices()[: spec["cell"]["chips"]], 9,
                        0.2, False, log=quiet)


def state_unchanged(monkeypatch, jc):
    import jax.numpy as jnp

    def unmoved(xl, plan, axis):
        n = min(plan.out_rows, xl.shape[0])
        out = jnp.zeros((plan.out_rows,) + xl.shape[1:], xl.dtype)
        return out.at[:n].set(xl[:n])

    monkeypatch.setattr(jc, "alltoallv_shard", unmoved)


def no_exchange(monkeypatch, jc):
    import jax

    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis, perm: x)


def half_the_steps(monkeypatch, jc):
    apply = jc._apply_steps
    monkeypatch.setattr(jc, "_apply_steps", lambda buf, steps, *a, **k:
                        apply(buf, steps[: len(steps) // 2], *a, **k))


def answer_altered(monkeypatch, jc):
    shard = jc.alltoallv_shard

    def altered(*a, **k):
        out = shard(*a, **k)
        return out.at[0].set(out[1])

    monkeypatch.setattr(jc, "alltoallv_shard", altered)


@pytest.mark.parametrize("fault", [state_unchanged, no_exchange,
                                   half_the_steps, answer_altered])
def test_exchange_fault_is_caught(fault, monkeypatch, jc):
    fault(monkeypatch, jc)
    out = run_tiny("mixtral-8x7b-ep4.train")
    assert not out["correct"] and out["failed"] > 0


def slab_state_unchanged(monkeypatch, jc):
    monkeypatch.setattr(slab, "_pass", lambda ops, steps: lambda buf, r: buf)


def slab_half_the_steps(monkeypatch, jc):
    make = slab._pass
    monkeypatch.setattr(slab, "_pass", lambda ops, steps:
                        make(ops, steps[: len(steps) // 2]))


def slab_row_altered(monkeypatch, jc):
    ops = jc._slab_ops

    def altered(reduce=False):
        extract, merge, step, view = ops(reduce)

        def bad_merge(buf, got, start, valid):
            return merge(buf, got.at[0].set(got[0] + 1), start, valid)

        return extract, bad_merge, step, view

    monkeypatch.setattr(jc, "_slab_ops", altered)


@pytest.mark.parametrize("cell", ["deepseek-moe-16b-ep4.slab",
                                  "mixtral-8x7b-ep4.slab"])
@pytest.mark.parametrize("fault", [slab_state_unchanged, slab_half_the_steps,
                                   slab_row_altered])
def test_slab_fault_is_caught(cell, fault, monkeypatch, jc):
    fault(monkeypatch, jc)
    out = run_tiny(cell)
    assert not out["correct"] and out["failed"] > 0
