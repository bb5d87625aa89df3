"""repro: Träff 2017 linear-time irregular gather/scatter as a first-class
JAX collective, inside a multi-pod training/serving framework.

Subpackages: core (the paper), tuner (autotuning planner service:
calibration, selection, plan cache), kernels (Pallas TPU), models,
configs, data, optim, train, checkpoint, runtime, launch, analysis.
See docs/ARCHITECTURE.md and EXPERIMENTS.md.
"""
__version__ = "1.0.0"
