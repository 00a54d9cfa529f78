"""Chip benchmark of the serving engine: one cell per run, driven by the
entries of ``BENCHMARK.json`` and the data files beside this package."""
