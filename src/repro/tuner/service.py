"""PlannerService: the calibrate → enumerate → select → cache pipeline as
one serving-shaped object covering gatherv / scatterv / allgatherv /
alltoallv and the reduction collectives reduce_scatterv / allreducev.

A service instance owns

* the calibrated :class:`~repro.core.costmodel.CostParams` (from a
  :class:`~repro.tuner.calibrate.Calibration`, or the ``tpu_ici``
  SI-units default),
* a :class:`~repro.tuner.cache.PlanCache` (persistent when ``cache_dir``
  is given) of *lowered* plans keyed by (op, p, quantized m-signature,
  root, dtype, mesh fingerprint),
* a bounded LRU of compiled shard_map executables (mesh required), and
* optionally a measurement loop: a ``measure`` callable races the top-k
  candidates and an :class:`~repro.tuner.calibrate.OnlineCalibrator`
  refits (α, β) from the observations after every race.

Planning works without any devices (``mesh=None``): ``plan``/
``plan_record`` select among the *executable* data-plane candidates under
the calibrated parameters and return the lowered plan.  Sizes quantize to
``quantum`` multiples first, so an adversarial stream of ragged sizes
maps onto a bounded set of signatures (and the MoE dispatch path replans
in O(1) once warm — see ``benchmarks/tuner_bench.py``).

Selection costs are computed in BYTES: row counts are scaled by
``row_bytes`` (feature width x itemsize) so the α-vs-β balance — which
decides e.g. how many bucket rounds pay off — is physical, not
row-count-relative.

Hierarchical meshes: pass ``topology=HostTopology(hosts, dev_per_host)``
(inferred automatically from a real multi-process mesh) and either a
:class:`~repro.core.costmodel.HierarchicalCostParams` as ``params`` or a
:class:`~repro.tuner.calibrate.HierarchicalCalibration` — the service
then races the two-level schedules against the flat ones under per-link
(α, β) and keys the plan cache by the host split, so a 2x4 and a 4x2
machine never share plans.  Hierarchical races refit online through a
:class:`~repro.tuner.calibrate.HierarchicalOnlineCalibrator` (one
4-weight observation per race), so per-axis observations are kept, not
dropped.

Telemetry (``repro.obs``): every service owns a metrics
:class:`~repro.obs.metrics.Registry` (cache hits, compiled LRU traffic,
races, executions), per-link-class residual ledgers comparing each
EXECUTED collective's measured seconds against its model prediction,
and a :class:`~repro.obs.guidelines_monitor.GuidelineMonitor` checking
the paper's G2–G4 bounds live.  A residual ledger's CUSUM detector
firing triggers :meth:`refit_from_residuals`: (α, β) are refit per link
class from the post-shift observations and ``params_epoch`` is bumped —
the epoch is part of every :class:`~repro.tuner.cache.PlanKey`, so all
plans selected under the stale model stop resolving at once.  Every
plan lookup (``plan/<op>``) and execution (``exec/<op>``) is a
``repro.obs.trace.span``: in a running profiler's trace always, and
recorded for the Chrome-trace exporter when ``repro.obs.trace`` is
enabled.
"""
from __future__ import annotations

import uuid
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from repro.core import opttrees
from repro.core.costmodel import (CostParams, DegradedCostParams,
                                  HierarchicalCostParams, HostTopology,
                                  LinkHealthMap)
from repro.obs import trace as obs_trace
from repro.obs.guidelines_monitor import GuidelineMonitor
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY, Registry
from repro.obs.residuals import DriftDetector, ResidualLedger

from .cache import (PlanCache, PlanKey, mesh_fingerprint, quantize_matrix,
                    quantize_sizes)
from .calibrate import (Calibration, HierarchicalCalibration,
                        HierarchicalOnlineCalibrator, OnlineCalibrator,
                        flat_weights, hierarchical_weights)
from .candidates import OPS, enumerate_candidates, plan_pipeline_cost
from .select import Selection, select


# telemetry settings no caller changes: the guideline monitor's slack over
# the padded-regular bound, the residual CUSUM's allowance (in log ratio)
# and each residual ledger's length
GUIDELINE_SLACK = 1.25
DRIFT_K = 0.5
MAX_RESIDUALS = 512


def _moved_row_bytes(row_bytes: int, dtype: str) -> int:
    """``jax_collectives.moved_row_bytes``, imported on first use: the
    planner itself needs no JAX."""
    from repro.core.jax_collectives import moved_row_bytes
    return moved_row_bytes(int(row_bytes), dtype)


@dataclass(frozen=True)
class PlanRecord:
    """What the cache stores: the lowered plan plus how it was chosen.

    ``serial`` is a globally unique id minted when the record is created;
    compiled executables are keyed by it, so a re-planned signature (after
    eviction, with possibly different selection) can never execute a stale
    schedule compiled for the old plan.
    """

    op: str
    plan: object                           # GathervPlan | ComposedPlan
    algo: str                              # winning candidate name
    costs: tuple[tuple[str, float], ...]   # full scoreboard at plan time
    serial: str = ""
    row_bytes: int = 1                     # bytes of one row, as given
    moved_row_bytes: int = 1               # what the ppermutes move per row


class _RowScaledCalibrator:
    """Adapter: dataplane candidate weights are in ROWS of the current
    problem; the calibrator's ledger is in BYTES.  Scale n_beta up by the
    row width before recording, so the fitted beta stays seconds-per-byte
    instead of compounding row_bytes on every refit."""

    def __init__(self, inner, row_bytes: int):
        self._inner = inner
        self._row_bytes = int(row_bytes)

    def observe(self, n_alpha: float, n_beta: float, seconds: float) -> None:
        self._inner.observe(n_alpha, n_beta * self._row_bytes, seconds)

    def observe_candidate(self, candidate, seconds: float) -> None:
        self._inner.observe_candidate(candidate, seconds,
                                      row_bytes=self._row_bytes)


class PlannerService:
    """Autotuned, cached planning (and execution) for irregular collectives.

    ``mesh=None`` gives a plan-only service (benchmarks, tests without
    devices); with a mesh, the six ops execute host arrays through one
    cached program per plan record and the host layout of
    :mod:`repro.core.jax_collectives` (``stage``/``unstage``).
    """

    def __init__(self, mesh=None, axis_name: str = "x", quantum: int = 128,
                 calibration=None,
                 params=None,
                 cache: PlanCache | None = None,
                 cache_dir: str | None = None,
                 max_cached_plans: int = 256,
                 max_compiled: int = 64,
                 buckets=(1, 2, 4),
                 segments=(1, 2, 4, 8),
                 wave_bins=(2.0,),
                 hysteresis: float = 0.05,
                 measure=None, top_k: int = 3,
                 calibrator=None,
                 topology: HostTopology | None = None,
                 metrics: Registry | None = None,
                 drift_h: float = 4.0,
                 drift_warmup: int = 8,
                 refit_window: int = 8,
                 refit_prior_weight: float = 4.0,
                 auto_refit: bool = True,
                 health: LinkHealthMap | None = None):
        self.mesh = mesh
        self.axis = axis_name
        self.quantum = int(quantum)
        # host topology: explicit beats mesh-inferred (plan-only services
        # have no mesh to infer from); it keys the cache and gates the
        # hierarchical two-level candidates
        self.topology = (topology if topology is not None
                         else HostTopology.from_mesh(mesh))
        if calibration is not None and isinstance(calibration,
                                                  HierarchicalCalibration):
            if self.topology is None or self.topology.hosts < 2:
                raise ValueError("a HierarchicalCalibration needs a "
                                 "multi-host topology")
            cal_params = calibration.cost_params(self.topology)
        elif calibration is not None:
            cal_params = calibration.cost_params()
        else:
            cal_params = None
        if params is not None and cal_params is not None:
            params.require_compatible(cal_params)
        self.params = (params if params is not None
                       else (cal_params if cal_params is not None
                             else CostParams.tpu_ici()))
        self.params.validate()
        if isinstance(self.params, HierarchicalCostParams):
            # the params' host mapping must be THE topology candidates and
            # cache keys use — a mismatch would silently price ICI hops as
            # DCN (and cache the wrong plan under the right fingerprint)
            if self.topology is None:
                self.topology = self.params.topology
            elif self.params.topology != self.topology:
                raise ValueError(
                    f"params topology {self.params.topology} != service "
                    f"topology {self.topology}")
        self.cache = cache if cache is not None else PlanCache(
            cache_dir, max_entries=max_cached_plans)
        self.buckets = tuple(buckets)
        self.segments = tuple(segments)
        # payload-bin ratios enumerated as wave-packed composed variants
        # (geometric bins bound within-step padding on skewed matrices)
        self.wave_bins = tuple(wave_bins)
        self.hysteresis = float(hysteresis)
        self.measure = measure
        self.top_k = int(top_k)
        self.calibrator = calibrator
        hier = isinstance(self.params, HierarchicalCostParams)
        if calibrator is not None:
            if hier:
                if not isinstance(calibrator, HierarchicalOnlineCalibrator):
                    raise ValueError(
                        "hierarchical params need a "
                        "HierarchicalOnlineCalibrator (the flat 2-weight "
                        "ledger cannot attribute a race across two link "
                        "classes)")
                self.params.require_compatible(calibrator.prior)
            else:
                if isinstance(calibrator, HierarchicalOnlineCalibrator):
                    raise ValueError("flat params with a hierarchical "
                                     "calibrator — pass an OnlineCalibrator")
                # the refit loop rewrites self.params from the calibrator,
                # so the starting params must already be in its units
                self.params.require_compatible(calibrator.prior.cost_params())
        elif measure is not None and hier:
            # hierarchical races used to measure candidates and then drop
            # the observations from refitting (PR 6 counted the drop and
            # warned once); a per-link-class calibrator keeps them
            self.calibrator = HierarchicalOnlineCalibrator(self.params)
        # key token -> algo name; LRU-bounded alongside the plan cache
        self._incumbent: OrderedDict[str, str] = OrderedDict()
        self._compiled: OrderedDict[tuple, object] = OrderedDict()
        self.max_compiled = int(max_compiled)
        self.compiled_hits = 0
        self.compiled_misses = 0
        self.last_selection: Selection | None = None
        # ------------------------------------------------- telemetry plane
        self.metrics = metrics if metrics is not None else Registry()
        if self.cache.metrics is None:
            self.cache.metrics = self.metrics
        self.guidelines = GuidelineMonitor(slack=GUIDELINE_SLACK)
        self.params_epoch = 0
        self.drift_refits = 0
        # ---------------------------------------------------- health plane
        # per-rank link slowdown overlay: selection prices every candidate
        # on the DEGRADED machine (DegradedCostParams), health-aware tree
        # variants join the race, and the health fingerprint keys the plan
        # cache so healthy-machine plans never serve a degraded one
        self.health = health if health is not None else LinkHealthMap()
        # last incident token that bumped the epoch: one fault incident may
        # be reported by several detectors (per-link CUSUM + host ladder);
        # it must invalidate the cache once, not once per detector
        self._last_incident: object | None = None
        self.auto_refit = bool(auto_refit)
        self.refit_window = int(refit_window)
        self.refit_prior_weight = float(refit_prior_weight)
        # one residual ledger per link class: drift is usually per-fabric,
        # and per-class rows are what refit_from_residuals refits from
        def _ledger(cls: str) -> ResidualLedger:
            return ResidualLedger(cls, max_observations=MAX_RESIDUALS,
                                  detector=DriftDetector(k=DRIFT_K,
                                                         h=drift_h,
                                                         warmup=drift_warmup))
        self.ledgers = ({"ici": _ledger("ici"), "dcn": _ledger("dcn")}
                        if hier else {"flat": _ledger("flat")})
        # the first call of a freshly jitted executable is dominated by
        # XLA compilation; flag it so its time never enters the ledger
        self._just_compiled = False

    # ------------------------------------------------------------ planning

    def bucketed(self, sizes) -> tuple[int, ...]:
        return quantize_sizes(sizes, self.quantum)

    def _key(self, op: str, arg, root: int | None, dtype: str,
             row_bytes: int, moved: int | None = None) -> PlanKey:
        if moved is None:
            moved = _moved_row_bytes(row_bytes, dtype)
        if op == "alltoallv":
            sig = quantize_matrix(arg, self.quantum)
            p = len(sig)
        else:
            sig = quantize_sizes(arg, self.quantum)
            p = len(sig)
        mesh = mesh_fingerprint(self.mesh, self.topology)
        hf = self.health.fingerprint()
        if hf:
            # health keys the cache directly (belt) in addition to the
            # epoch bump on every health change (suspenders): a plan
            # selected on a degraded machine never serves the healed one
            mesh = f"{mesh}|{hf}"
        width = f"{dtype}r{int(row_bytes)}"
        if moved != row_bytes:        # rows padded on this data plane
            width += f"m{int(moved)}"
        return PlanKey(op, p, sig, -1 if root is None else int(root),
                       width, mesh, epoch=self.params_epoch)

    def _sel_params(self, row_bytes: int):
        """Selection/prediction params in BYTES: per-row β scaled by the
        row width (shared by planning, residual pricing, and tracing)."""
        rb = max(1, int(row_bytes))
        if isinstance(self.params, HierarchicalCostParams):
            base = self.params.scale_data(rb)
        else:
            base = CostParams(self.params.alpha, self.params.beta * rb,
                              self.params.time_unit, "row")
        if self.health.is_trivial():
            return base
        # price candidates on the machine we actually have: degraded
        # links scale (α, β) per edge, so fault-aware shapes win the
        # argmin exactly when they are faster on the degraded fabric
        return DegradedCostParams(base, self.health)

    def plan_record(self, op: str, arg, root: int | None = None,
                    dtype: str = "float32", row_bytes: int = 1) -> PlanRecord:
        """Cached plan for one problem; a miss runs enumerate + select +
        lower and stores the result (write-through when persistent).
        Every call, hit or miss, is one ``plan/<op>`` span
        (``repro.obs.trace.span``) with the arg ``hit``."""
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}")
        if op in ("gatherv", "scatterv") and root is None:
            raise ValueError(f"{op} needs a root")
        with obs_trace.span("plan/" + op, "planner", op=op) as sp:
            moved = _moved_row_bytes(row_bytes, dtype)
            # the module registry: a reader that holds no service finds it
            _OBS_REGISTRY.gauge("moved_row_bytes").set(moved)
            key = self._key(op, arg, root, dtype, row_bytes, moved)
            rec = self.cache.get(key)
            sp.args["hit"] = rec is not None
            if rec is None:
                rec = self._plan_miss(op, key, root, row_bytes, moved,
                                      sp.args)
        return rec

    def _plan_miss(self, op: str, key: PlanKey, root: int | None,
                   row_bytes: int, moved: int,
                   span_args: dict) -> PlanRecord:
        """Enumerate, select and lower the plan of ``key``, store it, and
        fill the ``plan/<op>`` span's args with how it was chosen.  The
        plan is priced at ``moved`` bytes a row, what its ppermutes move
        (``jax_collectives.moved_row_bytes`` of ``row_bytes``)."""
        qarg = key.signature
        # selection params in bytes: scale the per-row β by the row width
        rb = max(1, int(moved))
        sel_params = self._sel_params(rb)
        cands = enumerate_candidates(op, qarg, root, sel_params,
                                     view="dataplane", buckets=self.buckets,
                                     segments=self.segments,
                                     wave_bins=self.wave_bins,
                                     topology=self.topology,
                                     health=self.health)
        cal = self.calibrator
        if cal is not None:
            cal = _RowScaledCalibrator(cal, rb)
        # measure contract: measure(candidate, row_bytes=...) -> seconds;
        # dataplane candidate weights are in rows, so the executor gets the
        # row width (a wall-clock executor is free to ignore it)
        meas = self.measure
        if meas is not None:
            meas = (lambda c, _m=self.measure, _rb=rb:
                    _m(c, row_bytes=_rb))
        # hysteresis incumbent is per SIGNATURE: it stabilizes re-planning
        # of the same problem (post-eviction, refitted params) and never
        # biases a brand-new problem away from its argmin
        token = key.token()
        sel = select(cands, sel_params, previous=self._incumbent.get(token),
                     hysteresis=self.hysteresis, measure=meas,
                     top_k=self.top_k, calibrator=cal)
        self.last_selection = sel
        self._incumbent[token] = sel.chosen
        self._incumbent.move_to_end(token)
        while len(self._incumbent) > self.cache.max_entries:
            self._incumbent.popitem(last=False)  # bounded like the plan cache
        if self.calibrator is not None and sel.measured:
            # online loop: the next selection uses the sharpened fit
            # (HierarchicalOnlineCalibrator.fitted IS the params object;
            # the flat Calibration wraps one).  Race-driven sharpening
            # does NOT bump the params epoch — only drift does: the fit
            # moves smoothly, cached plans stay honestly priced.
            fit = self.calibrator.fitted()
            self.params = (fit if isinstance(fit, HierarchicalCostParams)
                           else fit.cost_params())
        rec = PlanRecord(op=op, plan=sel.candidate(cands).build(),
                         algo=sel.chosen, costs=sel.costs,
                         serial=uuid.uuid4().hex, row_bytes=int(row_bytes),
                         moved_row_bytes=rb)
        self.cache.put(key, rec)
        self.metrics.counter("plans_planned").inc()
        if sel.measured:
            self.metrics.counter("candidates_raced").inc(len(sel.measured))
        span_args.update(
            p=key.p, token=token, algo=sel.chosen, cost=sel.cost,
            epoch=self.params_epoch, row_bytes=int(row_bytes),
            moved_row_bytes=rb, candidates=len(cands),
            raced=[n for n, _ in sel.measured] if sel.measured else [],
            kept_previous=sel.kept_previous)
        return rec

    def plan(self, op: str, arg, root: int | None = None,
             dtype: str = "float32", row_bytes: int = 1):
        return self.plan_record(op, arg, root, dtype, row_bytes).plan

    @property
    def plan_hits(self) -> int:
        return self.cache.hits

    @property
    def plan_misses(self) -> int:
        return self.cache.misses

    # ----------------------------------------------------------- execution

    def _compiled_fn(self, kind: str, rec: PlanRecord, F: int,
                     dtype_str: str):
        from repro.core import jax_collectives as jc

        ckey = (rec.serial, kind, F, dtype_str, jc.dataplane())
        fn = self._compiled.get(ckey)
        if fn is not None:
            self._compiled.move_to_end(ckey)
            self.compiled_hits += 1
            self.metrics.counter("compiled_lru_hits").inc()
            self._just_compiled = False
            return fn
        self.compiled_misses += 1
        self.metrics.counter("compiled_lru_misses").inc()
        self._just_compiled = True
        fn = jc.shard_program(kind, rec.plan, self.mesh, self.axis)
        self._compiled[ckey] = fn
        while len(self._compiled) > self.max_compiled:
            self._compiled.popitem(last=False)
        return fn

    def _put(self, x):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(self.mesh, P(self.axis)))

    # ----------------------------------------------------------- telemetry

    def _run(self, op: str, rec: PlanRecord, fn, x, arg=None,
             root: int | None = None) -> np.ndarray:
        """Execute a compiled plan inside the ``exec/<op>`` span under the
        step deadline and fault hook (``jax_collectives
        .call_with_deadline``), count it, and deposit its wall time into
        the residual/guideline plane, priced at the row bytes the plan was
        priced at."""
        from repro.core.jax_collectives import call_with_deadline

        fresh = self._just_compiled
        row_bytes = rec.moved_row_bytes
        args = {}
        if obs_trace.current() is not None:
            args = self._exec_span_args(op, rec, row_bytes, fresh)
        with obs_trace.span("exec/" + op, "collective", **args) as sp:
            out, dt, attempts = call_with_deadline(
                op, lambda: np.asarray(fn(self._put(x))))
            sp.args.update(measured_s=dt, attempts=attempts)
        self.metrics.counter("collectives_executed").inc()
        if not fresh:
            # a freshly jitted executable's first call is dominated by XLA
            # compilation — wall time says nothing about the fabric
            self.record_execution(op, rec, dt, row_bytes=row_bytes,
                                  arg=arg, root=root)
        return out

    def _exec_span_args(self, op: str, rec: PlanRecord, row_bytes: int,
                        fresh: bool) -> dict:
        rb = max(1, int(row_bytes))
        plan = rec.plan
        args = {"op": op, "algo": rec.algo, "serial": rec.serial,
                "segments": getattr(plan, "segments", 1),
                "num_stages": getattr(plan, "num_stages", 0),
                "predicted_s": plan_pipeline_cost(plan,
                                                  self._sel_params(rb)),
                "fresh_compile": fresh, "epoch": self.params_epoch,
                "row_bytes": rb}
        for cls, nbytes in obs_trace.plan_link_bytes(
                plan.steps, self.topology, row_bytes=rb).items():
            args[f"bytes_{cls}"] = nbytes
        return args

    def record_execution(self, op: str, rec: PlanRecord, measured_s: float,
                         row_bytes: int = 1, arg=None,
                         root: int | None = None,
                         incident: object | None = None) -> bool:
        """Deposit one executed collective into the telemetry plane.

        Prices the plan under the CURRENT byte-scaled params, records
        the log(measured/predicted) residual — with the plan's
        (α, β)-weight row — into the link class that dominates its
        predicted time, and checks the paper guideline when the size
        argument is supplied.  A detector fire triggers
        :meth:`refit_from_residuals` when ``auto_refit`` is set.
        Returns True iff drift was detected.  Benchmarks with model-
        consistent synthetic measurements call this directly; the
        execution methods call it with wall-clock seconds.
        """
        rb = max(1, int(row_bytes))
        plan = rec.plan
        tu = self.params.time_unit
        # snapshot the health overlay INTO the closure: a collective run
        # on a degraded link is slow because the link is slow, not because
        # the base (α, β) drifted — pricing it on the degraded machine
        # keeps honest residuals near zero (no false CUSUM fire), and
        # drift refits keep fitting the CLEAN base parameters
        _h = self.health

        def _overlay(P, __h=_h):
            return P if __h.is_trivial() else DegradedCostParams(P, __h)

        if isinstance(self.params, HierarchicalCostParams):
            # byte-unit cost closure: maps BYTE-unit params to the
            # plan's predicted seconds (the row-width scaling lives
            # inside), so refit iterations can re-derive weights at any
            # candidate params without knowing the row width
            def cost_fn(P, _plan=plan, _rb=rb, _ov=_overlay):
                return plan_pipeline_cost(_plan, _ov(P.scale_data(_rb)))

            predicted = float(cost_fn(self.params))
            weights = hierarchical_weights(cost_fn, self.params)
            ici_t = (weights[0] * self.params.ici.alpha
                     + weights[1] * self.params.ici.beta)
            dcn_t = (weights[2] * self.params.dcn.alpha
                     + weights[3] * self.params.dcn.beta)
            cls = "dcn" if dcn_t >= ici_t else "ici"
        else:
            def cost_fn(P, _plan=plan, _rb=rb, _tu=tu, _ov=_overlay):
                return plan_pipeline_cost(
                    _plan,
                    _ov(CostParams(P.alpha, P.beta * _rb, _tu, "row")))

            predicted = float(cost_fn(self.params))
            weights = flat_weights(cost_fn, self.params)
            cls = "flat"
        fired = self.ledgers[cls].record(op, predicted, float(measured_s),
                                         weights, cost_fn=cost_fn)
        self.metrics.counter("residuals_recorded").inc()
        if arg is not None:
            rep = self.guidelines.check(
                op, arg, float(measured_s), self.params,
                root=0 if root is None else int(root), row_bytes=rb)
            if rep is not None and not rep["ok"]:
                self.metrics.counter("guideline_violations").inc()
        if fired:
            self.metrics.counter("drift_detected").inc()
            tr = obs_trace.current()
            if tr is not None:
                tr.instant("drift/" + cls, "drift", op=op, link_class=cls,
                           predicted_s=predicted,
                           measured_s=float(measured_s))
            if self.auto_refit:
                self.refit_from_residuals(incident=incident)
        return fired

    # -------------------------------------------------------- health plane

    def _bump_epoch(self, incident: object | None = None) -> bool:
        """Invalidate every cached plan — at most once per incident.

        One physical fault typically trips several detectors (the
        per-link-class CUSUM and the straggler host ladder see the same
        slow step); callers tag both reports with the same ``incident``
        token and the cache flushes once.  ``incident=None`` always
        bumps (the pre-fault drift path keeps its semantics)."""
        if incident is not None and incident == self._last_incident:
            return False
        if incident is not None:
            self._last_incident = incident
        self.params_epoch += 1
        self.metrics.gauge("params_epoch").set(self.params_epoch)
        tr = obs_trace.current()
        if tr is not None:
            tr.instant("refit/epoch_bump", "drift",
                       epoch=self.params_epoch,
                       incident=repr(incident) if incident is not None
                       else None)
        return True

    def update_link_health(self, factors: dict | None = None,
                           hosts: dict | None = None,
                           alpha_factors: dict | None = None,
                           incident: object | None = None) -> bool:
        """Overlay new link-health observations and replan if they changed.

        ``factors`` maps RANK -> β slowdown factor (1.0 clears the rank);
        ``hosts`` maps HOST -> factor and is expanded over the host's
        ranks through the service topology.  A changed map bumps the
        params epoch (guarded by ``incident``), so every stale plan dies
        by key construction and the next request re-races the candidates
        — now including the health-aware tree shapes — on the degraded
        cost surface.  Returns True iff the map changed."""
        new = self.health
        if hosts:
            hm = LinkHealthMap.from_hosts(hosts, self.topology)
            new = new.merged(dict(hm.factors), dict(hm.alpha_factors))
        if factors or alpha_factors:
            new = new.merged(factors or {}, alpha_factors or {})
        if new == self.health:
            return False
        self.health = new
        self.metrics.counter("health_updates").inc()
        self.metrics.gauge("degraded_ranks").set(
            len(self.health.degraded_ranks()))
        self._bump_epoch(incident)
        return True

    def clear_link_health(self, incident: object | None = None) -> bool:
        """Drop the whole overlay (links healed / faults repaired)."""
        if self.health.is_trivial():
            return False
        self.health = LinkHealthMap()
        self.metrics.gauge("degraded_ranks").set(0)
        self._bump_epoch(incident)
        return True

    def refit_from_residuals(self, incident: object | None = None) -> None:
        """Drift response: refit (α, β) from the post-shift residual rows
        and bump ``params_epoch`` (at most once per ``incident``).

        The epoch is part of every PlanKey, so the bump invalidates all
        cached plans priced under the stale model at once — the next
        request replans (and re-selects) under the refit parameters.
        The refit pools the most recent ``refit_window`` rows of every
        ledger (post-shift measurements — older ones described the old
        regime) into the matching online calibrator with the CURRENT
        params as ridge prior, so an axis the rows do not constrain
        stays pinned instead of drifting to zero.
        """
        resids = []
        for led in self.ledgers.values():
            take = self.refit_window
            shift = led.detector.last_run_length
            if shift:
                # the fired ledger truncates to the CUSUM changepoint
                # estimate: rows from before the shift describe the old
                # regime, and least squares is not robust to them
                take = min(take, shift)
            resids.extend(led.recent(take))
        hier = isinstance(self.params, HierarchicalCostParams)

        def _fit_from(start):
            # iterated reweighted fit: each pass re-derives every
            # residual's weight row AT the current iterate (a large
            # shift moves plans into a different linear piece, so the
            # row stored at record time misprices the new regime).  The
            # ridge prior stays anchored at the PRE-refit params: a
            # window of same-shaped plans has near-collinear weight
            # rows, and the anchor keeps the axes the data cannot
            # identify at their last calibrated value.
            params = start
            for _ in range(3):
                if hier:
                    cal = HierarchicalOnlineCalibrator(
                        self.params, prior_weight=self.refit_prior_weight)
                    for r in resids:
                        if r.cost_fn is not None:
                            cal.observe(
                                hierarchical_weights(r.cost_fn, params),
                                r.measured_s)
                        elif len(r.weights) == 4:
                            cal.observe(r.weights, r.measured_s)
                    params = cal.fitted()
                else:
                    prior = Calibration(self.params.alpha,
                                        self.params.beta,
                                        r2=1.0, n_samples=0,
                                        backend="drift-refit")
                    cal = OnlineCalibrator(
                        prior, prior_weight=self.refit_prior_weight)
                    for r in resids:
                        if r.cost_fn is not None:
                            na, nb = flat_weights(r.cost_fn, params)
                            cal.observe(na, nb, r.measured_s)
                        elif len(r.weights) == 2:
                            cal.observe(r.weights[0], r.weights[1],
                                        r.measured_s)
                    fit = cal.fitted()
                    params = CostParams(fit.alpha_s, fit.beta_s_per_byte,
                                        self.params.time_unit,
                                        self.params.data_unit)
            return params

        def _sse(params):
            # prediction error under the candidate fit, evaluated with
            # the full piecewise cost (piece-aware, unlike the rows)
            e, n = 0.0, 0
            for r in resids:
                if r.cost_fn is None:
                    continue
                d = float(r.cost_fn(params)) - r.measured_s
                e += d * d
                n += 1
            return e if n else float("inf")

        # the iteration is only locally convergent: a fit biased by
        # stale-piece rows can sit in a self-consistent wrong piece.
        # Multi-start it from each axis scaled by the observed mean
        # ratio (a multiplicative drift hypothesis per axis) and keep
        # the converged fit that best predicts the actual measurements.
        ratio = float(np.exp(np.mean([r.log_ratio for r in resids]))
                      if resids else 1.0)
        cur = self.params
        if hier:
            tu, du = cur.time_unit, cur.data_unit

            def _scaled(si, sd):
                return HierarchicalCostParams(
                    CostParams(cur.ici.alpha * si, cur.ici.beta * si,
                               tu, du),
                    CostParams(cur.dcn.alpha * sd, cur.dcn.beta * sd,
                               tu, du), cur.topology)

            starts = [cur, _scaled(ratio, 1.0), _scaled(1.0, ratio),
                      _scaled(ratio, ratio)]
        else:
            starts = [cur,
                      CostParams(cur.alpha * ratio, cur.beta,
                                 cur.time_unit, cur.data_unit),
                      CostParams(cur.alpha, cur.beta * ratio,
                                 cur.time_unit, cur.data_unit),
                      CostParams(cur.alpha * ratio, cur.beta * ratio,
                                 cur.time_unit, cur.data_unit)]
        fits = [_fit_from(s) for s in starts]
        self.params = min(fits, key=_sse)
        self._bump_epoch(incident)
        self.drift_refits += 1
        if self.calibrator is not None:
            # rebase the race calibrator too: its old prior (and pre-drift
            # observations) describe the dead regime and would drag the
            # next race-driven fit straight back to it
            if isinstance(self.calibrator, HierarchicalOnlineCalibrator):
                self.calibrator = HierarchicalOnlineCalibrator(
                    self.params, self.calibrator.prior_weight)
            else:
                self.calibrator = OnlineCalibrator(
                    Calibration(self.params.alpha, self.params.beta,
                                r2=1.0, n_samples=0, backend="drift-refit"),
                    self.calibrator.prior_weight)
        for led in self.ledgers.values():
            led.reset_after_refit()
        self.metrics.counter("drift_refits").inc()

    def _execute(self, op: str, data, sizes=None, root: int | None = None):
        """Run ``op`` on host arrays (``jax_collectives.stage``) through
        the cached plan and program of their quantized signature: the
        blocks are staged at the quantized strides and the true rows read
        back.  Returns ``(result, plan)``."""
        from repro.core import jax_collectives as jc

        if self.mesh is None:
            raise RuntimeError("execution needs a mesh; this PlannerService "
                               "is plan-only (mesh=None)")
        sizes, F, dt = jc.host_rows(op, data, sizes)
        if len(sizes) != self.mesh.devices.size:
            raise ValueError(f"problem over {len(sizes)} ranks on a "
                             f"{self.mesh.devices.size}-device mesh")
        rec = self.plan_record(op, sizes, root=root, dtype=str(dt),
                               row_bytes=F * dt.itemsize)
        fn = self._compiled_fn(op, rec, F, str(dt))
        strides = (quantize_matrix(sizes, self.quantum) if op == "alltoallv"
                   else self.bucketed(sizes))
        out = self._run(op, rec, fn, jc.stage(op, rec.plan, data, sizes,
                                              strides),
                        arg=sizes, root=root)
        return jc.unstage(op, rec.plan, out, sizes, strides), rec.plan

    def gatherv(self, blocks: list[np.ndarray], root: int):
        """Gather ragged blocks to ``root``; returns ((sum(sizes), F) true
        rows in rank order, plan)."""
        return self._execute("gatherv", blocks, root=root)

    def scatterv(self, data: np.ndarray, sizes, root: int):
        """Scatter rank-ordered rows of ``data`` into ragged blocks;
        returns (list of (n_i, F) blocks, plan)."""
        return self._execute("scatterv", data, sizes, root=root)

    def allgatherv(self, blocks: list[np.ndarray], root: int | None = None):
        """Every device ends with all true blocks in rank order; returns
        ((p, sum(sizes), F) array, plan)."""
        return self._execute("allgatherv", blocks, root=root)

    def alltoallv(self, blocks: list[list[np.ndarray]]):
        """``blocks[i][j]``: block rank i sends to rank j.  Returns (list of
        per-device received buffers — device j's is ``concat_i blocks[i][j]``
        — and the plan)."""
        return self._execute("alltoallv", blocks)

    def reduce_scatterv(self, contribs: list[np.ndarray], sizes):
        """Sum the per-device flat contribution vectors (true layout, zero
        rows padding each segment to its quantized stride, so the true
        rows' sums are exact); rank ``j`` keeps segment ``j``.  Returns
        (list of (sizes[j], F) reduced blocks, plan)."""
        return self._execute("reduce_scatterv", contribs, sizes)

    def allreducev(self, contribs: list[np.ndarray], sizes):
        """Sum the per-device flat contribution vectors; returns ((p,
        sum(sizes), F) array, every rank's copy of the sum, and plan)."""
        return self._execute("allreducev", contribs, sizes)

    @property
    def stats(self) -> dict:
        if isinstance(self.params, HierarchicalCostParams):
            params = ("hier",
                      (self.params.ici.alpha, self.params.ici.beta),
                      (self.params.dcn.alpha, self.params.dcn.beta),
                      self.params.time_unit, self.params.data_unit)
        else:
            params = (self.params.alpha, self.params.beta,
                      self.params.time_unit, self.params.data_unit)
        return {**self.cache.stats,
                "compiled": len(self._compiled),
                "compiled_hits": self.compiled_hits,
                "compiled_misses": self.compiled_misses,
                "params": params,
                "params_epoch": self.params_epoch,
                "drift_refits": self.drift_refits,
                "link_health": dict(self.health.factors),
                "residuals": {cls: led.stats()
                              for cls, led in self.ledgers.items()},
                "guidelines": self.guidelines.summary(),
                "opt_memo": opttrees.memo_stats(),
                "metrics": self.metrics.snapshot()}
