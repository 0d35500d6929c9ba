"""A later change adds a metric, a configuration or a traffic mix with
files and entries alone: the harness finds each by its name."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def copy_tree(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def test_a_metric_file_and_entry_are_enough(tmp_path):
    root = copy_tree(tmp_path)
    (root / "chipbench" / "metrics" / "dummy_rounds.py").write_text(
        "def read(rec):\n    return 2 * rec['rounds']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "dummy_rounds", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "cluster.simulator",
        "moves": "sim_hours_per_s", "workloads": ["paper-steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import json, sys; sys.path.insert(0, 'chipbench'); import run\n"
        "bench = json.load(open('BENCHMARK.json'))\n"
        "rec = {'rounds': 21, 'round_s': [0.1] * 21, 'between_s': [0.01] * 21,"
        " 'pack_spans': None, 'trace': None, 'peaks': None,"
        " 'compiles_in_window': 0}\n"
        "print(json.dumps([run.metrics_of(bench, c, True, rec)"
        " for c in ('paper-steady', 'fleet1k-steady')]))\n")
    out = subprocess.run([sys.executable, "-c", script], cwd=root,
                         capture_output=True, text=True, check=True)
    paper, fleet = json.loads(out.stdout)
    assert paper["dummy_rounds"] == {"value": 42.0, "unit": "count"}
    assert "dummy_rounds" not in fleet
    assert paper["window_compiles"]["value"] == 0.0


def test_a_config_traffic_and_cell_file_are_enough(tmp_path):
    root = copy_tree(tmp_path)
    cfg = json.loads((root / "chipbench" / "configs" /
                      "eva-alibaba-1k.json").read_text())
    cfg["name"] = "eva-alibaba-2k"
    cfg["mean_interarrival_s"] /= 2
    (root / "chipbench" / "configs" / "eva-alibaba-2k.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "jittered.json").write_text(
        json.dumps({"arrivals": "poisson", "backlog": True, "gap_block": 64}))
    (root / "chipbench" / "cells" / "fleet2k-jittered.json").write_text(
        (root / "chipbench" / "cells" / "fleet1k-steady.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "eva-alibaba-2k", "source": "x",
                             "file": "chipbench/configs/eva-alibaba-2k.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "fleet2k-jittered",
                               "config": "eva-alibaba-2k",
                               "traffic": "jittered", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import json, sys; sys.path[:0] = ['chipbench', %r]; import run\n"
        "from chipbench import generator\n"
        "b, cell, cfg, traffic, harness = run.load_cell('fleet2k-jittered')\n"
        "jobs = generator.make_jobs(cfg, traffic, 3, rate_scale=0.01)\n"
        "print(json.dumps([generator.backlog_size(cfg), harness['cell'],"
        " [j.arrival_time for j in jobs]]))\n" % os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", script], cwd=root,
                         capture_output=True, text=True, check=True)
    n, harness_of, times = json.loads(out.stdout)
    assert n == 2000
    assert harness_of == "fleet1k-steady"
    t = np.asarray(times)
    assert np.sum(t == 0.0) == 20 and np.all(np.diff(t) >= 0)


def test_no_system_under_test_no_result(tmp_path):
    root = copy_tree(tmp_path)
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
