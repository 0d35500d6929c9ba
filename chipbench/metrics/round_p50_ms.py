"""Median wall time of ``schedule()`` over every round of the window (ms)."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["round_s"], 50)) * 1e3
