"""The trace reduction, on hand-made intervals and on a trace recorded on
a TPU v5e (``data/``, written by ``capture_trace.py``; ``expected.json``
holds the reduction of that file)."""
import json
import os

import pytest

from chipbench import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000  # ns


def events():
    host = [("chipbench.window", 0, 100 * MS),
            ("chipbench.sim", 0, 10 * MS),
            ("chipbench.schedule", 10 * MS, 60 * MS),
            ("jax_pack", 20 * MS, 50 * MS),
            ("chipbench.sim", 60 * MS, 100 * MS)]
    modules = [("jit__pack_all_types", 25 * MS, 40 * MS),
               ("jit_log", 38 * MS, 45 * MS),
               ("jit_other", 70 * MS, 75 * MS),
               ("jit_late", 120 * MS, 130 * MS)]
    return {"host": host, "modules": modules}


def test_busy_union_kernel_time_and_gaps():
    red = tracing.reduce_events(events(), "_pack_all_types")
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.025)  # 25-45 and 70-75 ms
    assert red["kernel_s"] == pytest.approx(0.015)
    assert red["kernel_calls"] == 1
    gaps = dict(red["idle_gaps"])
    # 0-10 sim, 10-20 schedule, 20-25 pack, 45-50 pack, 50-60 schedule,
    # 60-70 and 75-100 sim
    assert gaps == pytest.approx({"chipbench.sim": 0.045,
                                  "chipbench.schedule": 0.020,
                                  "jax_pack": 0.010})
    assert sum(gaps.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])
    ops = dict(red["device_ops"])
    assert ops == pytest.approx({"jit__pack_all_types": 0.015,
                                 "jit_log": 0.007, "jit_other": 0.005})


def test_one_window_span_is_required():
    ev = events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracing.reduce_events(ev, "_pack_all_types")


def test_recorded_chip_trace():
    path = os.path.join(DATA, "paper_steady.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    import shutil
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        prof = os.path.join(d, "plugins", "profile", "run")
        os.makedirs(prof)
        shutil.copy(path, os.path.join(prof, "host.xplane.pb"))
        ev = tracing.load(d)
    red = tracing.reduce_events(ev, "_pack_all_types")
    with open(os.path.join(DATA, "expected.json")) as f:
        want = json.load(f)
    assert red["kernel_calls"] == want["kernel_calls"] > 0
    for key in ("window_s", "busy_s", "kernel_s"):
        assert red[key] == pytest.approx(want[key], rel=1e-12)
    assert 0 < red["kernel_s"] <= red["busy_s"] < red["window_s"]
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9
    assert {n for n, _ in red["idle_gaps"]} <= {
        "chipbench.sim", "chipbench.schedule", "jax_pack", "other"}
