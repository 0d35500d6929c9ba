"""Simulated hours the window advanced over its wall seconds: rounds,
event handling and accrual together."""

def read(rec):
    return rec["sim_hours"] / rec["window_s"]
