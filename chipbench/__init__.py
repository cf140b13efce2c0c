"""Chip benchmark of the LSM store: see run.py and PERF.md."""
