"""Compiled-artifact analysis: HLO collective-byte accounting and roofline
terms (EXPERIMENTS.md §Roofline)."""
from .hlo import collective_bytes_from_hlo, CollectiveStats  # noqa: F401
