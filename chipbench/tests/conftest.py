"""Tests of the benchmark itself, run apart from the repository's suite:

    python3 -m pytest -q chipbench/tests

They run on the CPU, with four virtual devices for the multi-chip
rehearsals; the flags are set before JAX is imported."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
