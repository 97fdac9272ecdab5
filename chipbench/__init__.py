"""Chip benchmark of the MaRI serving runtime: one cell per run, driven by
``BENCHMARK.json`` at the repository root (see ``run.py``)."""
