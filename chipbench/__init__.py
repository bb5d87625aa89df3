"""Benchmark of the ragged collectives on the chip."""
