"""Autotuning planner service for the irregular collectives.

The paper's headline is that the right algorithm is a FUNCTION of the
machine parameters (α, β) and the size vector — ``3⌈log₂p⌉α + βΣmᵢ``
beats fixed binomial trees in some regimes and loses to flat linear
trees in others.  This package turns that observation into a runtime
pipeline, the way production MPI libraries and NCCL's PAT select
schedules:

* :mod:`~repro.tuner.calibrate` — fit (α, β) per mesh/axis from
  ping-pong + bisection micro-measurements (deterministic synthetic
  backend for device-free tests), plus the online refit loop;
* :mod:`~repro.tuner.candidates` — the full schedule space already
  latent in the repo behind one :class:`Candidate` interface;
* :mod:`~repro.tuner.select` — model-guided argmin with optional
  measured racing and hysteresis;
* :mod:`~repro.tuner.cache` — persistent, versioned, LRU-bounded plan
  cache keyed by (op, p, quantized m-signature, root, dtype, mesh);
* :mod:`~repro.tuner.service` — :class:`PlannerService`, the six ops'
  serving front end — gatherv/scatterv/allgatherv/alltoallv plus the
  reduction collectives reduce_scatterv/allreducev, executed through
  the one host layout and program of :mod:`repro.core.jax_collectives`;
* :mod:`~repro.tuner.classifier` / :mod:`~repro.tuner.serving` — the
  decode-time continuous-batching layer: raw per-step size vectors map
  onto bounded padded signature classes (padding priced under α-β,
  overhead ≤ a configured bound), predicted next classes are planned
  and compiled off the hot path, and the steady-state serving loop is
  replan- and recompile-free.
"""
from .cache import (CACHE_VERSION, PlanCache, PlanKey,  # noqa: F401
                    mesh_fingerprint, quantize_matrix, quantize_sizes)
from .classifier import SignatureClassifier  # noqa: F401
from .calibrate import (Calibration, HierarchicalCalibration,  # noqa: F401
                        HierarchicalOnlineCalibrator, MeshTimingBackend,
                        OnlineCalibrator, SyntheticHierarchicalBackend,
                        SyntheticTimingBackend, calibrate, calibrate_axes,
                        fit_alpha_beta, flat_weights, hierarchical_weights)
from .candidates import (Candidate, OPS,  # noqa: F401
                         enumerate_candidates, plan_pipeline_cost,
                         plan_step_cost)
from .select import Selection, argmin_name, select  # noqa: F401
from .service import PlanRecord, PlannerService  # noqa: F401
from .serving import ServingPlanner, SignaturePredictor  # noqa: F401
