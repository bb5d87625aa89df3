"""Telemetry-plane tests: trace recorder + Chrome export, metrics
registry, residual ledger + CUSUM drift detection, guideline monitors,
the per-host straggler feed, and the end-to-end drift → refit →
epoch-bump → re-selection loop through PlannerService.

The drift e2e is the PR's keystone scenario: a synthetic machine whose
β degrades 32x mid-run must (a) fire the detector, (b) refit (α, β)
from the post-shift residuals, (c) bump ``params_epoch`` so every
cached plan stops resolving, and (d) re-select a candidate that is
genuinely cheaper on the degraded machine.  A no-drift control with
the same noise level must never bump the epoch.
"""
from __future__ import annotations

import doctest
import json
import math
import os

import numpy as np
import pytest

from repro.core.costmodel import CostParams, HostTopology
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.guidelines_monitor import GuidelineMonitor, padded_regular_rhs
from repro.obs.metrics import Histogram, Registry
from repro.obs.residuals import DriftDetector, ResidualLedger
from repro.obs.trace import TraceRecorder, plan_link_bytes
from repro.runtime.straggler import StragglerPolicy
from repro.tuner import PlannerService, plan_pipeline_cost


class _FakeClock:
    """Deterministic clock for span-timing assertions."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def recorder():
    """Fresh module-level recorder, restoring whatever was active (the
    CI obs lane runs the whole suite under REPRO_TRACE=1)."""
    prev = obs_trace.current()
    rec = obs_trace.enable(TraceRecorder())
    yield rec
    if prev is None:
        obs_trace.disable()
    else:
        obs_trace.enable(prev)


def _svc(**kw) -> PlannerService:
    kw.setdefault("params", CostParams(2e-6, 2.5e-11, "s", "byte"))
    return PlannerService(quantum=1, **kw)


def _t_under(rec, p: CostParams) -> float:
    """Synthetic 'measured' seconds: the plan priced under machine
    params ``p`` (row_bytes=1, matching the service's selection
    pricing)."""
    return plan_pipeline_cost(
        rec.plan, CostParams(p.alpha, p.beta, p.time_unit, "row"))


# ---------------------------------------------------------------- trace


class TestTraceRecorder:
    def test_span_context_manager(self):
        clk = _FakeClock()
        rec = TraceRecorder(clock=clk)
        with rec.span("exec/gatherv", cat="collective", p=8) as h:
            clk.t = 0.25
            h.args["measured_s"] = 0.25
        (s,) = rec.events
        assert s.name == "exec/gatherv" and s.cat == "collective"
        assert s.ph == "X"
        assert s.ts == 0.0 and s.dur == pytest.approx(0.25)
        assert s.args == {"p": 8, "measured_s": 0.25}

    def test_add_complete_and_instant(self):
        clk = _FakeClock()
        rec = TraceRecorder(clock=clk)
        rec.add_complete("plan/gatherv", "planner", 1.0, 0.5, tid=3, op="g")
        clk.t = 2.0
        rec.instant("drift/flat", "drift", link_class="flat")
        a, b = rec.events
        assert a.ph == "X" and a.ts == 1.0 and a.dur == 0.5 and a.tid == 3
        assert b.ph == "i" and b.ts == 2.0 and b.dur == 0.0
        assert b.args["link_class"] == "flat"

    def test_trim_keeps_first_events(self):
        rec = TraceRecorder(max_events=3)
        for i in range(10):
            rec.add_complete(f"s{i}", "c", float(i), 1.0)
        assert [e.name for e in rec.events] == ["s0", "s1", "s2"]
        assert rec.dropped == 7
        assert rec.to_chrome_trace()["otherData"]["dropped_events"] == 7

    def test_max_events_validated(self):
        with pytest.raises(ValueError):
            TraceRecorder(max_events=0)

    def test_chrome_export_schema(self):
        clk = _FakeClock()
        rec = TraceRecorder(clock=clk)
        rec.add_complete("a", "c", 1.0, 0.5, op="x", n=np.int64(3),
                         payloads=(1, np.float64(2.5)), plan=object())
        rec.instant("drift/flat", "drift")
        doc = rec.to_chrome_trace(pid=7)
        json.dumps(doc)                      # everything is JSON-safe
        ev, inst = doc["traceEvents"]
        assert ev["ph"] == "X" and ev["pid"] == 7
        assert ev["ts"] == pytest.approx(1.0e6)       # microseconds
        assert ev["dur"] == pytest.approx(0.5e6)
        assert float(ev["args"]["n"]) == 3.0          # numpy scalar coerced
        assert ev["args"]["payloads"] == [1, 2.5]
        assert isinstance(ev["args"]["plan"], str)    # repr fallback
        assert inst["ph"] == "i" and inst["s"] == "g" and "dur" not in inst

    def test_save_roundtrip(self, tmp_path):
        rec = TraceRecorder()
        with rec.span("exec/alltoallv", cat="collective", p=4):
            pass
        path = rec.save(str(tmp_path / "sub" / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"][0]["name"] == "exec/alltoallv"
        assert doc["otherData"]["recorder"] == "repro.obs.trace"

    def test_spans_query_and_span_times_by(self):
        clk = _FakeClock()
        rec = TraceRecorder(clock=clk)
        rec.add_complete("exec/gatherv", "collective", 0.0, 1.0, host=0)
        rec.add_complete("exec/gatherv", "collective", 0.0, 2.0, host=1)
        rec.add_complete("exec/gatherv", "collective", 2.0, 3.0, host=1)
        rec.add_complete("plan/gatherv", "planner", 0.0, 9.0, host=0)
        assert len(rec.spans(cat="collective")) == 3
        assert len(rec.spans(name_prefix="plan/")) == 1
        times = rec.span_times_by("host", cat="collective")
        assert times == {0: pytest.approx(1.0), 1: pytest.approx(5.0)}

    def test_enable_disable_current(self):
        prev = obs_trace.current()
        try:
            mine = TraceRecorder()
            assert obs_trace.enable(mine) is mine
            assert obs_trace.current() is mine
            assert obs_trace.enable() is mine     # idempotent when active
            obs_trace.disable()
            assert obs_trace.current() is None
        finally:
            if prev is None:
                obs_trace.disable()
            else:
                obs_trace.enable(prev)


def _steps(p, edges):
    """One synthetic lowered step: ``edges`` is [(src, dst, rows)]."""
    recv_valid = np.zeros(p, np.int64)
    perm = []
    for s, d, rows in edges:
        perm.append((s, d))
        recv_valid[d] = rows
    return [(tuple(perm), int(recv_valid.max()), None, None, recv_valid)]


class TestPlanLinkBytes:
    def test_flat(self):
        steps = _steps(4, [(0, 1, 3), (2, 3, 2)])
        assert plan_link_bytes(steps, None, row_bytes=4) == {"flat": 20}

    def test_hierarchical_split(self):
        topo = HostTopology(2, 2)              # devices {0,1} | {2,3}
        steps = _steps(4, [(0, 1, 3), (1, 3, 2)])
        out = plan_link_bytes(steps, topo, row_bytes=4)
        assert out == {"ici": 12, "dcn": 8}

    def test_single_host_topology_is_flat(self):
        topo = HostTopology(1, 4)
        steps = _steps(4, [(0, 1, 5)])
        assert plan_link_bytes(steps, topo, row_bytes=2) == {"flat": 10}


# -------------------------------------------------------------- metrics


class TestMetrics:
    def test_docstring_example(self):
        res = doctest.testmod(obs_metrics)
        assert res.attempted > 0 and res.failed == 0

    def test_architecture_doc_example(self):
        """The §Telemetry example in docs/ARCHITECTURE.md stays live."""
        doc = os.path.join(os.path.dirname(__file__), "..", "docs",
                           "ARCHITECTURE.md")
        res = doctest.testfile(doc, module_relative=False)
        assert res.attempted > 0 and res.failed == 0

    def test_counter_monotonic(self):
        reg = Registry()
        c = reg.counter("x")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge(self):
        g = Registry().gauge("epoch")
        g.set(3)
        g.inc()
        assert g.value == 4

    def test_histogram_buckets(self):
        h = Histogram("h", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0, float("nan")):
            h.observe(v)
        assert h.counts == [1, 1, 1]           # NaN dropped, overflow kept
        assert h.count == 3
        assert h.mean == pytest.approx((0.5 + 5.0 + 50.0) / 3)
        with pytest.raises(ValueError):
            Histogram("bad", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            Histogram("bad", buckets=())

    def test_registry_get_or_create_and_kind_conflict(self):
        reg = Registry()
        c = reg.counter("x")
        assert reg.counter("x") is c
        with pytest.raises(TypeError):
            reg.gauge("x")
        snap = reg.snapshot()
        assert snap["counters"] == {"x": 0}
        assert snap["gauges"] == {} and snap["histograms"] == {}
        json.dumps(snap)


# ------------------------------------------------- residuals and drift


class TestDriftDetector:
    def test_warmup_absorbs_systematic_bias(self):
        det = DriftDetector(k=0.5, h=4.0, warmup=8)
        for _ in range(8):
            assert not det.update(0.7)
        assert det.baseline == pytest.approx(0.7)
        rng = np.random.default_rng(1)
        for _ in range(100):
            assert not det.update(0.7 + rng.uniform(-0.3, 0.3))
        assert det.fired == 0 and det.stats()["warmed_up"]

    def test_fires_on_positive_shift_with_run_length(self):
        det = DriftDetector(k=0.5, h=4.0, warmup=4)
        for _ in range(4):
            det.update(0.0)
        assert not det.update(2.0)             # g+ = 1.5
        assert not det.update(2.0)             # g+ = 3.0
        assert det.update(2.0)                 # g+ = 4.5 > h: fire
        assert det.fired == 1
        assert det.last_run_length == 3        # excursion began 3 obs ago
        assert det.g_pos == 0.0 and det.g_neg == 0.0

    def test_fires_on_negative_shift(self):
        det = DriftDetector(k=0.5, h=2.0, warmup=2)
        det.update(0.0)
        det.update(0.0)
        assert not det.update(-1.5)
        assert not det.update(-1.5)
        assert det.update(-1.5)
        assert det.last_run_length == 3

    def test_nonfinite_ignored(self):
        det = DriftDetector(warmup=2)
        assert not det.update(float("nan"))
        assert not det.update(float("inf"))
        assert det.n == 0

    def test_reset(self):
        det = DriftDetector(k=0.5, h=1.0, warmup=1)
        det.update(0.0)
        while not det.update(3.0):
            pass
        det.reset()
        assert (det.n, det.baseline, det.last_run_length) == (0, 0.0, 0)
        det.update(0.5)
        det.reset(keep_baseline=True)
        assert det.n == 1 and det.baseline == pytest.approx(0.5)


class TestResidualLedger:
    def test_degenerate_observations_skipped(self):
        led = ResidualLedger()
        assert not led.record("gatherv", 0.0, 1.0)
        assert not led.record("gatherv", 1.0, -1.0)
        assert led.total == 0 and led.recent() == []

    def test_bounded_and_recent(self):
        led = ResidualLedger(max_observations=4)
        for i in range(10):
            led.record("op", 1.0, 1.0 + i)
        assert led.total == 10 and len(led.recent()) == 4
        assert [r.measured_s for r in led.recent(2)] == [9.0, 10.0]
        with pytest.raises(ValueError):
            ResidualLedger(max_observations=0)

    def test_residual_carries_weights_and_cost_fn(self):
        led = ResidualLedger()
        fn = lambda p: 1.0                                    # noqa: E731
        led.record("gatherv", 1.0, 2.0, weights=(4.0, 1e6), cost_fn=fn)
        (r,) = led.recent()
        assert r.weights == (4.0, 1e6)
        assert r.cost_fn is fn
        assert r.log_ratio == pytest.approx(math.log(2.0))

    def test_reset_after_refit(self):
        led = ResidualLedger(detector=DriftDetector(warmup=1))
        for _ in range(5):
            led.record("op", 1.0, 3.0)
        led.reset_after_refit()
        assert led.recent() == [] and led.refits == 1
        assert led.detector.n == 0
        assert led.total == 5                  # lifetime count survives

    def test_stats(self):
        led = ResidualLedger("dcn")
        led.record("op", 1.0, 2.0)
        st = led.stats()
        assert st["link_class"] == "dcn" and st["kept"] == 1
        assert st["mean_ratio"] == pytest.approx(2.0)
        assert st["detector"]["n"] == 1


# ----------------------------------------------------------- guidelines


class TestGuidelineMonitor:
    PARAMS = CostParams(2e-6, 2.5e-11, "s", "byte")

    def test_ok_violation_and_bounded_reports(self):
        mon = GuidelineMonitor(slack=1.25, keep_violations=2)
        m = [100, 2000, 50, 700]
        rhs = padded_regular_rhs("gatherv", m, self.PARAMS, root=0,
                                 row_bytes=4)
        assert rhs > 0
        rep = mon.check("gatherv", m, rhs * 0.5, self.PARAMS, root=0,
                        row_bytes=4)
        assert rep["ok"] and rep["guideline"] == "G2"
        for _ in range(3):
            rep = mon.check("gatherv", m, rhs * 2.0, self.PARAMS, root=0,
                            row_bytes=4)
        assert not rep["ok"]
        s = mon.summary()
        assert s["G2"] == {"checked": 4, "violations": 3}
        assert len(s["recent_violations"]) == 2

    def test_alltoallv_guideline(self):
        mon = GuidelineMonitor()
        S = [[0, 500, 20], [900, 0, 4], [7, 7, 0]]
        rhs = padded_regular_rhs("alltoallv", S, self.PARAMS, row_bytes=4)
        rep = mon.check("alltoallv", S, rhs, self.PARAMS, row_bytes=4)
        assert rep["ok"] and rep["guideline"] == "G4"

    def test_reductions_have_no_guideline(self):
        mon = GuidelineMonitor()
        assert mon.check("reduce_scatterv", [1, 2], 1.0, self.PARAMS) is None
        assert mon.check("allreducev", [1, 2], 1.0, self.PARAMS) is None
        assert mon.summary() == {"recent_violations": []}

    def test_slack_validated(self):
        with pytest.raises(ValueError):
            GuidelineMonitor(slack=0.0)


# ------------------------------------------------------- straggler feed


class TestStragglerHostFeed:
    def test_ladder_and_decay(self):
        pol = StragglerPolicy(factor=2.0, evict_after=3)
        base = {f"h{i}": 1.0 for i in range(4)}
        slow = dict(base, h0=5.0)
        assert pol.observe_hosts(0, slow)["h0"] == "warn"
        assert pol.observe_hosts(1, slow)["h0"] == "backup"
        assert pol.observe_hosts(2, slow)["h0"] == "evict"
        clean = pol.observe_hosts(3, base)
        assert clean["h0"] == "ok"
        assert pol.host_breaches["h0"] == 2            # decayed by one
        assert [e["action"] for e in pol.host_events] == \
            ["warn", "backup", "evict"]
        assert all(a == "ok" for h, a in pol.observe_hosts(0, slow).items()
                   if h != "h0")

    def test_too_few_hosts_is_ok(self):
        pol = StragglerPolicy(factor=2.0)
        assert pol.observe_hosts(0, {"a": 1.0, "b": 99.0}) == \
            {"a": "ok", "b": "ok"}

    def test_observe_trace_feed(self):
        clk = _FakeClock()
        rec = TraceRecorder(clock=clk)
        for h in range(4):
            rec.add_complete("exec/gatherv", "collective", 0.0, 1.0, host=h)
        rec.add_complete("exec/gatherv", "collective", 1.0, 5.0, host=2)
        rec.add_complete("plan/gatherv", "planner", 0.0, 99.0, host=0)
        pol = StragglerPolicy(factor=2.0)
        acts = pol.observe_trace(0, rec, cat="collective")
        assert acts[2] == "warn"               # 6.0 vs median-of-others 1.0
        assert all(acts[h] == "ok" for h in (0, 1, 3))

    def test_observe_trace_empty(self):
        pol = StragglerPolicy()
        assert pol.observe_trace(0, TraceRecorder()) == {}


# -------------------------------------------------- service integration


class TestServiceTelemetry:
    SIZES = [128, 4096, 32, 1024]

    def test_plan_span_on_hit_and_miss(self, recorder):
        svc = _svc()
        svc.plan_record("gatherv", self.SIZES, root=0, row_bytes=4)
        svc.plan_record("gatherv", self.SIZES, root=0, row_bytes=4)
        spans = recorder.spans(cat="planner", name_prefix="plan/gatherv")
        assert [s.args["hit"] for s in spans] == [False, True]
        miss, hit = (s.args for s in spans)
        assert miss["op"] == "gatherv" and miss["epoch"] == 0
        assert miss["candidates"] > 0 and miss["algo"]
        assert miss["cost"] > 0 and miss["row_bytes"] == 4
        assert hit == {"op": "gatherv", "hit": True}
        snap = svc.metrics.snapshot()["counters"]
        assert snap["plan_cache_misses"] == 1
        assert snap["plan_cache_hits"] == 1
        assert snap["plans_planned"] == 1

    def test_plan_spans_in_profiler_trace(self, tmp_path):
        """The planner's spans land in the host plane of a profiler trace,
        with or without a recorder."""
        import glob

        import jax

        svc = _svc()
        with jax.profiler.trace(str(tmp_path)):
            svc.plan_record("gatherv", self.SIZES, root=0, row_bytes=4)
            svc.plan_record("gatherv", self.SIZES, root=0, row_bytes=4)
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = [e.name for plane in pd.planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events]
        assert names.count("plan/gatherv") == 2

    def test_tracing_off_is_noop(self):
        prev = obs_trace.current()
        obs_trace.disable()
        try:
            assert obs_trace.current() is None
            svc = _svc()
            rec = svc.plan_record("gatherv", self.SIZES, root=0)
            assert svc.record_execution("gatherv", rec, _t_under(
                rec, svc.params), arg=self.SIZES, root=0) is False
        finally:
            if prev is not None:
                obs_trace.enable(prev)

    def test_record_execution_deposits(self):
        svc = _svc()
        rec = svc.plan_record("gatherv", self.SIZES, root=0)
        m = _t_under(rec, svc.params)
        assert not svc.record_execution("gatherv", rec, m, arg=self.SIZES,
                                        root=0)
        st = svc.stats
        assert st["residuals"]["flat"]["total"] == 1
        assert st["residuals"]["flat"]["last_ratio"] == pytest.approx(1.0)
        assert st["metrics"]["counters"]["residuals_recorded"] == 1
        assert st["guidelines"]["G2"]["checked"] == 1
        assert st["params_epoch"] == 0 and st["drift_refits"] == 0
        (r,) = svc.ledgers["flat"].recent()
        assert r.cost_fn is not None
        assert float(r.cost_fn(svc.params)) == pytest.approx(r.predicted_s)

    def test_params_epoch_changes_plan_key(self):
        svc = _svc(auto_refit=False)
        k0 = svc._key("gatherv", self.SIZES, 0, "float32", 4)
        svc.params_epoch = 1
        k1 = svc._key("gatherv", self.SIZES, 0, "float32", 4)
        assert k0 != k1 and k0.token() != k1.token()


# --------------------------------------------------------- drift e2e


ASSUMED = CostParams(2e-6, 2.5e-11, "s", "byte")
DEGRADED = CostParams(ASSUMED.alpha, ASSUMED.beta * 32, "s", "byte")


def _drift_service(**kw) -> PlannerService:
    return PlannerService(quantum=1, params=ASSUMED, refit_window=8,
                          refit_prior_weight=0.0, drift_h=4.0, **kw)


def _run_phase(svc, rng, n, machine, noise=0.0):
    """Plan + 'execute' n random gatherv problems under ``machine``;
    returns True if any execution fired the drift detector."""
    fired = False
    for _ in range(n):
        sizes = [int(s) for s in rng.integers(500, 20000, 16)]
        rec = svc.plan_record("gatherv", sizes, root=0)
        m = _t_under(rec, machine)
        if noise:
            m *= rng.uniform(1.0 - noise, 1.0 + noise)
        if svc.record_execution("gatherv", rec, m, arg=sizes, root=0):
            fired = True
            break
    return fired


class TestDriftEndToEnd:
    def test_drift_refit_epoch_bump_and_reselection(self, recorder):
        svc = _drift_service()
        # p = 17 keeps the DP optimal tree (exact for p <= OPT_P_MAX = 16)
        # out of the race: it wins under BOTH machines, so at p <= 16 the
        # re-selection below would correctly keep the same plan and the
        # tuw -> linear flip this test discriminates on would vanish.
        probe = list(range(1000, 18000, 1000))
        rec0 = svc.plan_record("gatherv", probe, root=0)
        assert svc.plan_record("gatherv", probe, root=0) is rec0   # hit
        rng = np.random.default_rng(0)

        # phase 1: machine matches the model (3% noise) — never fires
        assert not _run_phase(svc, rng, 10, ASSUMED, noise=0.03)
        assert svc.params_epoch == 0

        # phase 2: β degrades 32x — detector must fire within the phase
        assert _run_phase(svc, rng, 20, DEGRADED)
        assert svc.params_epoch == 1
        assert svc.drift_refits == 1
        assert svc.ledgers["flat"].refits == 1

        # the refit recovered the degraded machine from post-shift rows
        assert svc.params.alpha == pytest.approx(DEGRADED.alpha, rel=0.05)
        assert svc.params.beta == pytest.approx(DEGRADED.beta, rel=0.05)

        # epoch bump invalidated the cached probe plan by key construction
        misses0 = svc.plan_misses
        rec1 = svc.plan_record("gatherv", probe, root=0)
        assert svc.plan_misses == misses0 + 1

        # ... and re-selection flips to a plan genuinely cheaper on the
        # degraded machine (β-heavy regime favors bandwidth-optimal trees)
        assert rec1.algo != rec0.algo
        win = _t_under(rec0, DEGRADED) / _t_under(rec1, DEGRADED)
        assert win > 1.05

        # the drift episode is visible on the trace timeline
        drift_names = {s.name for s in recorder.spans(cat="drift")}
        assert "drift/flat" in drift_names
        assert "refit/epoch_bump" in drift_names
        snap = svc.metrics.snapshot()
        assert snap["counters"]["drift_detected"] == 1
        assert snap["counters"]["drift_refits"] == 1
        assert snap["gauges"]["params_epoch"] == 1

    def test_no_drift_control_never_bumps_epoch(self):
        svc = _drift_service()
        rng = np.random.default_rng(2)
        assert not _run_phase(svc, rng, 30, ASSUMED, noise=0.03)
        assert svc.params_epoch == 0
        assert svc.drift_refits == 0
        assert svc.ledgers["flat"].detector.fired == 0
