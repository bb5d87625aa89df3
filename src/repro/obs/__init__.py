"""Telemetry plane for the collective dataplane.

The planner predicts (``plan_step_cost`` / ``plan_pipeline_cost``),
selects, caches, and executes — but a model nobody audits rots silently
under congestion, throttling, or a degraded link.  This package is the
audit loop:

* :mod:`~repro.obs.trace` — per-lookup and per-collective structured
  spans, written into a running profiler's trace and, when recording
  is on, exported as Chrome-trace/Perfetto JSON;
* :mod:`~repro.obs.metrics` — pure-Python counters / gauges /
  histograms published by the plan cache, the compiled-executable LRU,
  the selection path, and the host-staged executions;
* :mod:`~repro.obs.residuals` — per-link-class measured-vs-predicted
  residual ledgers with a CUSUM drift detector; a detected shift
  triggers online refit and a params-epoch bump that honestly
  invalidates every cached plan priced under the stale model;
* :mod:`~repro.obs.guidelines_monitor` — the paper's G1–G4
  irregular-vs-regular guidelines asserted against live measurements.
"""
from .guidelines_monitor import (GUIDELINE_BY_OP,  # noqa: F401
                                 GuidelineMonitor, padded_regular_rhs)
from .metrics import (REGISTRY, Counter, Gauge,  # noqa: F401
                      Histogram, Registry)
from .residuals import DriftDetector, Residual, ResidualLedger  # noqa: F401
from .trace import (Span, TraceRecorder, current,  # noqa: F401
                    disable, enable, plan_link_bytes, span)
