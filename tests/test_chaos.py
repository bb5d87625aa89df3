"""Fault-aware runtime: chaos schedules, degraded-topology replanning,
deadline/retry, the straggler ladder acting end-to-end, elastic shrink.

Everything runs device-free: degraded machines are priced through
``DegradedCostParams`` and executed through the NumPy step oracle.
"""
from __future__ import annotations

import collections

import numpy as np
import pytest

from repro.core import jax_collectives as jc
from repro.core.baselines import two_level_tree
from repro.core.costmodel import (CostParams, DegradedCostParams,
                                  HierarchicalCostParams, HostTopology,
                                  LinkHealthMap, worst_alpha)
from repro.core.pipeline import (execute_steps_numpy, plan_host_times)
from repro.core import build_gather_tree, simulate_gather
from repro.runtime.chaos import (ChaoticMachine, ExecutionFaultInjector,
                                 FaultClock, FaultSchedule, HostLoss,
                                 HostStall, LinkDegrade, TimeoutFault,
                                 backup_swap, remap_root, shrink_matrix,
                                 shrink_sizes, surviving_ranks,
                                 unswap_blocks)
from repro.runtime.restart import HostEvicted, TrainLoop
from repro.runtime.straggler import StragglerPolicy
from repro.tuner import PlannerService, SyntheticTimingBackend
from repro.tuner.calibrate import SyntheticHierarchicalBackend


# ---------------------------------------------------------------- schedule

class TestFaultSchedule:
    def test_random_is_deterministic(self):
        a = FaultSchedule.random(4, 20, seed=3, loss_step=15)
        b = FaultSchedule.random(4, 20, seed=3, loss_step=15)
        assert a.events == b.events
        c = FaultSchedule.random(4, 20, seed=4, loss_step=15)
        assert a.events != c.events

    def test_step_queries(self):
        s = FaultSchedule.scripted(
            LinkDegrade(1, 8.0, start=2, end=5),
            HostStall(0, 3, 1e-3),
            TimeoutFault(4, op="gatherv", attempts=2),
            HostLoss(2, 6))
        assert s.host_factors(1) == {}
        assert s.host_factors(2) == {1: 8.0}
        assert s.host_factors(5) == {}
        assert s.stall_s(3, 0) == pytest.approx(1e-3)
        assert s.max_stall_s(3) == pytest.approx(1e-3)
        assert s.timeout_attempts(4, "gatherv") == 2
        assert s.timeout_attempts(4, "scatterv") == 0
        assert s.lost_hosts(5) == set()
        assert s.lost_hosts(6) == {2}
        assert s.loss_steps() == [6]

    def test_health_map_expansion(self):
        s = FaultSchedule.scripted(LinkDegrade(1, 4.0))
        topo = HostTopology(2, 4)
        hm = s.health_map(0, topo)
        assert hm.degraded_ranks() == {4: 4.0, 5: 4.0, 6: 4.0, 7: 4.0}
        flat = s.health_map(0)      # no topology: hosts ARE ranks
        assert flat.degraded_ranks() == {1: 4.0}


# ------------------------------------------------------------- cost overlay

class TestDegradedCostParams:
    def test_trivial_overlay_is_exact(self):
        m = [5, 9, 300, 2, 41, 7, 8, 1]
        t = build_gather_tree(m, root=0)
        base = CostParams.tpu_ici()
        wrapped = DegradedCostParams(base, LinkHealthMap())
        assert simulate_gather(t, wrapped) == simulate_gather(t, base)

    def test_degraded_costs_more(self):
        m = [5, 9, 300, 2, 41, 7, 8, 1]
        t = build_gather_tree(m, root=0)
        base = CostParams.tpu_ici()
        sick = DegradedCostParams(base, LinkHealthMap.from_factors({2: 16.0}))
        assert simulate_gather(t, sick) > simulate_gather(t, base)

    def test_worst_alpha_and_flat_attrs(self):
        base = CostParams.tpu_ici()
        d = DegradedCostParams(
            base, LinkHealthMap.from_factors({1: 2.0},
                                             alpha_factors={1: 3.0}))
        assert worst_alpha(d) == pytest.approx(base.alpha * 3.0)
        assert d.alpha == base.alpha and d.beta == base.beta

    def test_fingerprint_and_merge(self):
        h = LinkHealthMap.from_factors({2: 16.0, 5: 4.0})
        assert h.fingerprint().startswith("health[")
        healed = h.merged({2: 1.0})
        assert healed.degraded_ranks() == {5: 4.0}
        assert LinkHealthMap().fingerprint() == ""


# -------------------------------------------------------- health-aware trees

class TestHealthTrees:
    def test_degraded_rank_becomes_leaf(self):
        m = [8, 8, 100, 8, 8, 8, 8, 8]     # rank 2 interior when healthy
        healthy = build_gather_tree(m, root=0)
        assert healthy.children_of(2), "fixture: rank 2 must be interior"
        sick = build_gather_tree(m, root=0, health={2: 16.0})
        assert sick.children_of(2) == []
        assert "+health" in sick.name
        sick.validate(m)

    def test_two_level_avoids_degraded_host(self):
        topo = HostTopology(4, 4)
        m = [8] * 16
        m[5] = 200                          # host 1 would lead otherwise
        health = {r: 16.0 for r in range(4, 8)}
        t = two_level_tree(m, root=0, node_size=4, health=health)
        t.validate(m)
        # no edge crosses INTO the sick host from outside it
        for e in t.edges:
            if 4 <= e.parent < 8:
                assert 4 <= e.child < 8, \
                    f"edge {e.child}->{e.parent} enters the degraded host"

    def test_health_variant_wins_selection(self):
        svc = PlannerService(quantum=1)
        svc.update_link_health(factors={2: 16.0})
        rec = svc.plan_record("gatherv", [8, 8, 100, 8, 8, 8, 8, 8],
                              root=0, row_bytes=4)
        assert rec.algo.startswith("tuw_health")


# ------------------------------------------------------------ service plane

class TestServiceHealthPlane:
    def test_health_keys_cache_and_bumps_epoch(self):
        svc = PlannerService(quantum=1)
        m = [8, 8, 100, 8, 8, 8, 8, 8]
        k0 = svc._key("gatherv", m, 0, "float32", 4)
        assert svc.update_link_health(factors={2: 16.0})
        k1 = svc._key("gatherv", m, 0, "float32", 4)
        assert k0.token() != k1.token()
        assert k1.mesh.endswith(svc.health.fingerprint())
        assert svc.params_epoch == 1
        # no-change update: no bump, no flush
        assert not svc.update_link_health(factors={2: 16.0})
        assert svc.params_epoch == 1

    def test_single_incident_bumps_epoch_once(self):
        """One degraded link may be reported by BOTH the host ladder
        (update_link_health) and the per-link-class CUSUM
        (refit_from_residuals) — one incident, one cache flush."""
        svc = PlannerService(quantum=1)
        incident = ("fault", 5)
        assert svc.update_link_health(factors={2: 16.0}, incident=incident)
        assert svc.params_epoch == 1
        svc.refit_from_residuals(incident=incident)
        assert svc.params_epoch == 1          # same incident: no 2nd bump
        assert svc.drift_refits == 1          # the refit itself still ran
        svc.refit_from_residuals(incident=("fault", 9))
        assert svc.params_epoch == 2          # a NEW incident bumps
        svc.refit_from_residuals()            # None always bumps
        assert svc.params_epoch == 3

    def test_degraded_residuals_do_not_false_fire(self):
        """An exactly-degraded measurement prices as residual ~0: link
        health explains the slowdown, so the CUSUM must stay quiet."""
        from repro.tuner.candidates import plan_pipeline_cost
        svc = PlannerService(quantum=1, drift_warmup=2)
        svc.update_link_health(factors={2: 16.0})
        m = [8, 8, 100, 8, 8, 8, 8, 8]
        rec = svc.plan_record("gatherv", m, root=0, row_bytes=4)
        truth = DegradedCostParams(
            CostParams(svc.params.alpha, svc.params.beta * 4,
                       svc.params.time_unit, "row"), svc.health)
        for _ in range(12):
            fired = svc.record_execution(
                "gatherv", rec, plan_pipeline_cost(rec.plan, truth),
                row_bytes=4)
            assert not fired

    def test_clear_link_health(self):
        svc = PlannerService(quantum=1)
        svc.update_link_health(factors={2: 16.0})
        assert svc.stats["link_health"] == {2: 16.0}
        assert svc.clear_link_health()
        assert svc.stats["link_health"] == {}
        assert svc.params_epoch == 2
        assert not svc.clear_link_health()


# ------------------------------------------------------------ chaos machine

class TestChaoticMachine:
    def test_measure_prices_degraded_machine(self):
        from repro.tuner.candidates import enumerate_candidates
        sched = FaultSchedule.scripted(LinkDegrade(2, 16.0, start=1))
        backend = SyntheticTimingBackend()
        cm = ChaoticMachine(backend, sched)
        m = [8, 8, 100, 8, 8, 8, 8, 8]
        c = enumerate_candidates("gatherv", m, 0, backend.true_params(),
                                 view="dataplane")[0]
        clean = cm.measure(c)
        cm.advance(1)
        assert cm.measure(c) > clean

    def test_host_span_times_single_out_victim(self):
        sched = FaultSchedule.scripted(LinkDegrade(2, 16.0))
        cm = ChaoticMachine(SyntheticTimingBackend(), sched)
        svc = PlannerService(quantum=1)
        plan = svc.plan("gatherv", [8, 8, 100, 8, 8, 8, 8, 8], root=0)
        # large rows: β dominates, so the ×16 link singles the victim out
        spans = cm.host_span_times(plan, row_bytes=1_000_000)
        assert spans[2] == max(spans.values())

    def test_fault_clock_scales_calibration(self):
        sched = FaultSchedule.scripted(LinkDegrade(0, 16.0, start=0, end=1),
                                       HostStall(1, 0, 1e-3))
        clock = FaultClock(sched, pair_hosts=(0, 1))
        b = SyntheticTimingBackend(alpha_s=1e-6, beta_s_per_byte=1e-9,
                                   chaos=clock)
        clean = SyntheticTimingBackend(alpha_s=1e-6, beta_s_per_byte=1e-9)
        assert b.ping_pong(1000) == pytest.approx(
            clean.ping_pong(1000) * 16.0 + 1e-3)
        assert "chaos[" in b.fingerprint()
        clock.advance(1)                    # faults over: exact again
        assert b.ping_pong(1000) == pytest.approx(clean.ping_pong(1000))

    def test_hier_backend_chaos_on_dcn_only(self):
        topo = HostTopology(2, 4)
        sched = FaultSchedule.scripted(LinkDegrade(0, 4.0))
        clock = FaultClock(sched)
        b = SyntheticHierarchicalBackend(topo, chaos=clock)
        clean = SyntheticHierarchicalBackend(topo)
        assert b.dcn.ping_pong(1000) == pytest.approx(
            clean.dcn.ping_pong(1000) * 4.0)
        assert b.ici.ping_pong(1000) == pytest.approx(
            clean.ici.ping_pong(1000))


# ----------------------------------------------------------- deadline/retry

class TestDeadlineRetry:
    def teardown_method(self):
        jc.configure_step_deadline(None)
        jc.set_fault_hook(None)

    def test_transient_fault_absorbed_by_retry(self):
        sched = FaultSchedule.scripted(TimeoutFault(0, attempts=2))
        inj = ExecutionFaultInjector(sched).install()
        jc.configure_step_deadline(1.0, retries=2)
        out, _dt, attempts = jc.call_with_deadline("gatherv", lambda: 7)
        assert out == 7 and attempts == 3
        assert inj.injected == 2

    def test_persistent_fault_escalates(self):
        sched = FaultSchedule.scripted(TimeoutFault(0, attempts=99))
        ExecutionFaultInjector(sched).install()
        jc.configure_step_deadline(1.0, retries=2)
        with pytest.raises(jc.CollectiveTimeout) as ei:
            jc.call_with_deadline("gatherv", lambda: 7)
        assert ei.value.op == "gatherv"
        assert ei.value.attempts == 3

    def test_no_deadline_no_retry_overhead(self):
        out, _dt, attempts = jc.call_with_deadline("gatherv", lambda: 7)
        assert out == 7 and attempts == 1

    @pytest.mark.parametrize("path", ["service", "run_plan"])
    def test_host_staged_execution_retries_a_fault(self, path):
        """Both host-staged paths run their program under the deadline:
        an injected transient fault is retried and the result is exact."""
        import jax
        from jax.sharding import AxisType

        mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
        blocks = [np.arange(10, dtype=np.float32).reshape(5, 2)]
        inj = ExecutionFaultInjector(FaultSchedule.scripted(
            TimeoutFault(0, op="gatherv", attempts=1))).install()
        prev = jc.dataplane()
        jc.set_dataplane("xla")
        try:
            if path == "service":
                got, _ = PlannerService(mesh=mesh, axis_name="x",
                                        quantum=4).gatherv(blocks, root=0)
            else:
                got, _ = jc.run_plan("gatherv", jc.plan_gatherv([5], 0),
                                     mesh, "x", blocks)
        finally:
            jc.set_dataplane(prev)
        np.testing.assert_array_equal(got, blocks[0])
        assert inj.injected == 1


# ------------------------------------------------------------- straggler

class TestStragglerPolicy:
    def test_window_is_bounded_deque(self):
        pol = StragglerPolicy(window=8)
        for i in range(100):
            pol.observe(i, 0.1)
        assert isinstance(pol.times, collections.deque)
        assert pol.times.maxlen == 8 and len(pol.times) == 8

    def test_breaching_sample_kept_out_of_baseline(self):
        pol = StragglerPolicy(factor=2.0, window=8)
        for i in range(4):
            pol.observe(i, 0.1)
        assert pol.observe(4, 1.0) == "warn"
        assert 1.0 not in pol.times       # cannot drag its own median up
        assert pol.observe(5, 1.0) == "backup"
        assert pol.observe(6, 1.0) == "evict"

    def test_aggregate_decay_matches_ladder(self):
        pol = StragglerPolicy(factor=2.0)
        for i in range(4):
            pol.observe(i, 0.1)
        pol.observe(4, 1.0)
        pol.observe(5, 1.0)               # breaches = 2
        pol.observe(6, 0.1)               # clean: decay to 1
        assert pol.breaches == 1
        assert pol.observe(7, 1.0) == "backup"

    def test_all_zero_median_does_not_mask(self):
        pol = StragglerPolicy(factor=3.0)
        acts = pol.observe_hosts(0, {0: 0.0, 1: 0.0, 2: 0.0, 3: 0.5})
        assert acts[3] == "warn"          # others at 0: host 3 IS the stall
        assert acts[0] == "ok"

    def test_zero_everywhere_is_clean(self):
        pol = StragglerPolicy()
        acts = pol.observe_hosts(0, {0: 0.0, 1: 0.0, 2: 0.0})
        assert set(acts.values()) == {"ok"}

    def test_record_timeout_climbs_ladder(self):
        pol = StragglerPolicy()
        assert pol.record_timeout(0) == "warn"
        assert pol.record_timeout(1) == "backup"
        assert pol.record_timeout(2) == "evict"
        assert pol.record_timeout(0, host=4) == "warn"
        assert pol.host_health() == {4: pol.factor}

    def test_host_health_reports_measured_ratio(self):
        pol = StragglerPolicy(factor=2.0)
        pol.observe_hosts(0, {0: 0.1, 1: 0.1, 2: 0.1, 3: 1.0})
        assert pol.host_health()[3] == pytest.approx(10.0)
        # decay to zero forgets the host
        for step in range(1, 3):
            pol.observe_hosts(step, {0: 0.1, 1: 0.1, 2: 0.1, 3: 0.1})
        assert 3 not in pol.host_health()


# ------------------------------------------------------------ train loop

class _FakePipeline:
    def batch(self, step):
        return {}


def _mk_loop(tmp_path, **kw):
    state = {"w": np.zeros(4, np.float32)}
    loop = TrainLoop(
        step_fn=lambda s, b: (s, {"loss": 0.0}),
        pipeline=_FakePipeline(),
        ckpt_dir=str(tmp_path / "ckpt"),
        ckpt_every=100, **kw)
    return loop, state


class TestTrainLoopActs:
    def test_warn_feeds_planner_health(self, tmp_path):
        svc = PlannerService(quantum=1)
        spans = {0: 0.001, 1: 0.001, 2: 0.001, 3: 0.010}
        loop, state = _mk_loop(
            tmp_path, planner=svc,
            straggler=StragglerPolicy(factor=2.0, evict_after=99),
            host_times_fn=lambda step: spans)
        _, history = loop.run(state, 3)
        assert all(r["action"] != "ok" for r in history)
        assert all(r["host_actions"] == {3: r["action"]} for r in history)
        assert svc.health.degraded_ranks()[3] == pytest.approx(10.0)
        assert svc.params_epoch >= 1

    def test_evict_checkpoints_and_raises(self, tmp_path):
        svc = PlannerService(quantum=1)
        spans = {0: 0.001, 1: 0.001, 2: 0.001, 3: 0.010}
        loop, state = _mk_loop(
            tmp_path, planner=svc,
            straggler=StragglerPolicy(factor=2.0, evict_after=3),
            host_times_fn=lambda step: spans)
        with pytest.raises(HostEvicted) as ei:
            loop.run(state, 10)
        assert ei.value.host == 3
        assert ei.value.step == 2             # 3rd consecutive breach
        assert ei.value.checkpoint_step == 3
        # the barrier checkpoint is on disk for the elastic resume
        from repro.checkpoint import restore_latest
        restored, manifest = restore_latest(state, loop.ckpt_dir)
        assert manifest["step"] == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])

    def test_on_evict_handler_stops_cleanly(self, tmp_path):
        calls = []
        loop, state = _mk_loop(
            tmp_path,
            straggler=StragglerPolicy(factor=2.0, evict_after=1),
            host_times_fn=lambda step: {0: 0.001, 1: 0.001, 2: 0.001,
                                        3: 0.010},
            on_evict=lambda step, host: calls.append((step, host)))
        _, history = loop.run(state, 10)
        assert calls == [(0, 3)]
        assert len(history) == 1 and history[0]["action"] == "evict"


# ---------------------------------------------------------- elastic shrink

class TestElasticShrink:
    def test_shrink_helpers(self):
        sched = FaultSchedule.scripted(HostLoss(1, 4))
        surv = surviving_ranks(8, sched.lost_hosts(4),
                               topology=HostTopology(2, 4))
        assert surv == [0, 1, 2, 3]
        flat = surviving_ranks(4, {1})
        assert flat == [0, 2, 3]
        assert shrink_sizes([10, 20, 30, 40], flat) == [10, 30, 40]
        S = np.arange(16).reshape(4, 4)
        Sq = shrink_matrix(S, flat)
        assert Sq.shape == (3, 3) and Sq[0, 0] == 0 and Sq[1, 1] == 10
        assert remap_root(2, flat) == 1
        assert remap_root(1, flat) == 0   # dead root: first survivor

    def test_shrunk_gatherv_is_exact(self):
        rng = np.random.default_rng(0)
        sizes = [int(x) for x in rng.integers(1, 30, 8)]
        surv = surviving_ranks(8, {2})
        ssz = shrink_sizes(sizes, surv)
        root = remap_root(0, surv)
        svc = PlannerService(quantum=1)
        plan = svc.plan("gatherv", ssz, root=root)
        F = 2
        blocks = [rng.integers(0, 10**6, (s, F)) for s in ssz]
        bufs = np.zeros((7, plan.buf_rows, F), np.int64)
        for i, b in enumerate(blocks):
            bufs[i, plan.offsets[i]: plan.offsets[i] + len(b)] = b
        out = execute_steps_numpy(plan.steps, bufs)
        np.testing.assert_array_equal(
            out[root, : plan.total], np.concatenate(blocks, axis=0))

    def test_backup_swap_roundtrip(self):
        sizes = [10, 20, 30, 0]
        swapped = backup_swap(sizes, straggler=2, spare=3)
        assert swapped == [10, 20, 0, 30]
        blocks = ["a", "b", "spare-served", "c"]
        assert unswap_blocks(blocks, 2, 3) == ["a", "b", "c",
                                               "spare-served"]

    def test_shrink_consolidation(self):
        from repro.checkpoint import shrink_consolidation
        plan = shrink_consolidation([100, 200, 300, 400], lost_ranks={1},
                                    root=1)
        assert plan["survivors"] == [0, 2, 3]
        assert plan["rank_remap"] == {0: 0, 2: 1, 3: 2}
        assert plan["root"] == 0          # dead coordinator re-elected
        assert plan["n_shards"] == 3
        assert plan["total_bytes"] == 800


# ------------------------------------------------------------- e2e chaos

class TestChaosEndToEnd:
    def test_degraded_link_replanning_wins_and_matches_oracle(self):
        """The ISSUE acceptance: x16 degraded links -> health map ->
        replanned tree demotes the victim to a leaf -> >= 1.2x faster
        on the degraded machine -> byte-identical output."""
        from benchmarks.chaos_bench import degraded_link_leg
        _rows, payload = degraded_link_leg(quick=True)
        assert payload["aware"]["rows_into_victim"] == 0
        assert payload["oblivious"]["rows_into_victim"] > 0
        assert payload["speedup"] >= 1.2
        assert payload["byte_identical"]

    def test_host_loss_shrinks_all_collectives_exactly(self):
        from benchmarks.chaos_bench import host_loss_leg
        _rows, payload = host_loss_leg(quick=True)
        assert payload["ops_exact"] == ["gatherv", "allgatherv",
                                        "alltoallv", "reduce_scatterv",
                                        "allreducev"]
        assert len(payload["survivors"]) == payload["p"] - 1

    def test_plan_host_times_hier(self):
        topo = HostTopology(2, 4)
        hp = HierarchicalCostParams(CostParams(1e-6, 1e-9, "s", "byte"),
                                    CostParams(1e-5, 1e-8, "s", "byte"),
                                    topo)
        svc = PlannerService(quantum=1, params=hp, topology=topo)
        plan = svc.plan("gatherv", [10] * 8, root=0)
        spans = plan_host_times(plan.steps, 8, hp, topology=topo)
        assert set(spans) == {0, 1}
        assert all(s > 0 for s in spans.values())
