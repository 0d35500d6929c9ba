"""Chip benchmark of the Eva scheduler: see run.py and PERF.md."""
