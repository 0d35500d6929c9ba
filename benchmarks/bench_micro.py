"""Tables 4 & 5: provisioning-cost micro-benchmark (No-Packing vs Full
Reconfiguration vs ILP), Full-Reconfiguration runtime scaling (plus the
beyond-paper jitted JAX engine), and the fleet-scale planning curve
(10³→10⁶ tasks: numpy vs single-pass jit vs incremental repack)."""
from __future__ import annotations

import time

import numpy as np

from repro.core import (LiveInstance, TaskSet, aws_catalog, cheapest_type,
                        full_reconfiguration, incremental_reconfiguration,
                        make_task, reservation_prices)
from repro.core.catalog import FAMILIES, NUM_RESOURCES
from repro.core.ilp import cost_lower_bound, solve_ilp
from repro.core.workloads import NUM_WORKLOADS, WORKLOADS
from repro.obs import profiler as _prof

from . import common
from .common import print_table, save_results


def _random_tasks(n, rng):
    return TaskSet([make_task(job_id=i, workload=int(rng.integers(NUM_WORKLOADS)))
                    for i in range(n)])


def array_fleet(n, rng):
    """Array-built fleet (single-task jobs): the (W, F, R) profile matrix is
    gathered per task, so construction stays O(n) with no Python loop."""
    prof = np.zeros((NUM_WORKLOADS, len(FAMILIES), NUM_RESOURCES))
    for wi, w in enumerate(WORKLOADS):
        for fi, fam in enumerate(FAMILIES):
            prof[wi, fi] = w.demand_for_family(fam)
    wl = rng.integers(NUM_WORKLOADS, size=n).astype(np.int64)
    ids = np.arange(n, dtype=np.int64)
    return TaskSet.from_arrays(ids, ids, wl, prof[wl])


def table4(trials=5, n_tasks=200, ilp_time_limit=30.0, quick=False):
    """Provisioning cost for a static task set (paper: ILP ~1×, Full
    Reconfig 1.01×, No-Packing 1.56×; Gurobi timed out at 30 min)."""
    if quick:
        trials, n_tasks, ilp_time_limit = 3, 60, 10.0
    cat = aws_catalog()
    rows = []
    ratios_np, ratios_fr, gaps = [], [], []
    t_fr = t_ilp = 0.0
    for t in range(trials):
        rng = np.random.default_rng(1000 + t)
        tasks = _random_tasks(n_tasks, rng)
        rp = reservation_prices(tasks, cat)
        no_packing = float(rp.sum())
        t0 = time.time()
        cfg = full_reconfiguration(tasks, cat, table=None,
                                   interference_aware=False,
                                   multi_task_aware=False)
        t_fr += time.time() - t0
        fr_cost = cfg.total_hourly_cost(cat)
        t0 = time.time()
        ilp = solve_ilp(tasks, cat, time_limit_s=ilp_time_limit)
        t_ilp += time.time() - t0
        base = min(ilp.cost, fr_cost) if ilp.config else fr_cost
        lb = max(cost_lower_bound(tasks, cat), ilp.lower_bound)
        ratios_np.append(no_packing / base)
        ratios_fr.append(fr_cost / base)
        gaps.append(base / max(lb, 1e-9))
    rows.append({"scheduler": "No-Packing",
                 "norm_cost": f"{np.mean(ratios_np):.2f}±{np.std(ratios_np):.2f}",
                 "runtime_ms": "<1"})
    rows.append({"scheduler": "Full-Reconfig",
                 "norm_cost": f"{np.mean(ratios_fr):.3f}±{np.std(ratios_fr):.3f}",
                 "runtime_ms": round(t_fr / trials * 1e3, 1)})
    rows.append({"scheduler": f"ILP(HiGHS,{ilp_time_limit:.0f}s)",
                 "norm_cost": "1.00 (best found)",
                 "runtime_ms": round(t_ilp / trials * 1e3, 1)})
    rows.append({"scheduler": "LP/resource lower bound",
                 "norm_cost": f"best/LB={np.mean(gaps):.3f}",
                 "runtime_ms": ""})
    print_table("Table 4: provisioning-cost micro-benchmark", rows,
                ["scheduler", "norm_cost", "runtime_ms"])
    return rows


def table5(sizes=(1000, 2000, 4000, 8000), quick=False):
    """Full Reconfiguration runtime scaling.  Paper (Python): 0.4 / 1.5 /
    5.5 / 22.1 s.  Ours: vectorized numpy engine + jitted JAX engine."""
    if quick:
        sizes = (500, 1000)
    cat = aws_catalog()
    rows = []
    for n in sizes:
        rng = np.random.default_rng(n)
        tasks = _random_tasks(n, rng)
        t0 = time.time()
        c_np = full_reconfiguration(tasks, cat, table=None, engine="numpy",
                                    interference_aware=False,
                                    multi_task_aware=False)
        dt_np = time.time() - t0
        # jax engine: warm up once (compile), then time
        t0 = time.time()
        full_reconfiguration(tasks, cat, table=None, engine="jax",
                             interference_aware=False, multi_task_aware=False)
        dt_warm = time.time() - t0
        t0 = time.time()
        c_jx = full_reconfiguration(tasks, cat, table=None, engine="jax",
                                    interference_aware=False,
                                    multi_task_aware=False)
        dt_jx = time.time() - t0
        rows.append({"n_tasks": n,
                     "paper_python_s": {1000: 0.40, 2000: 1.50, 4000: 5.53,
                                        8000: 22.06}.get(n, "n/a"),
                     "numpy_s": round(dt_np, 3),
                     "jax_jit_s": round(dt_jx, 3),
                     "jax_warmup_s": round(dt_warm, 3),
                     "cost_numpy": round(c_np.total_hourly_cost(cat), 1),
                     "cost_jax": round(c_jx.total_hourly_cost(cat), 1)})
    print_table("Table 5: Full Reconfiguration runtime", rows,
                ["n_tasks", "paper_python_s", "numpy_s", "jax_jit_s",
                 "jax_warmup_s", "cost_numpy", "cost_jax"])
    return rows


#: numpy engine is O(T·K·fills) in Python-visible work; past this it takes
#: minutes per row, so larger rows report the jit/incremental columns only.
NUMPY_CAP = 10_000


def scaling_curve(sizes=(1000, 10_000, 100_000, 1_000_000), quick=False):
    """Fleet-scale planning curve: single-pass jitted engine vs numpy, plus
    incremental repack latency for a single-instance disturbance.

    Columns: ``numpy_s`` (capped at NUMPY_CAP tasks), ``jax_s`` (warm jitted
    full re-plan), ``jax_warmup_s`` (first call: compile + shape-bucket
    retraces), ``jax_compile_s`` (the jit-compile share of warmup, from the
    engine's ``jax_pack`` profiler spans; measured only when recording is
    on), ``incremental_s`` (one evacuated instance, dirty-set repack), and
    the two speedup ratios the CI gate pins.
    """
    if quick:
        sizes = (1000, 10_000, 100_000)
    cat = aws_catalog()
    kw = dict(interference_aware=False, multi_task_aware=True)
    # the profiler rides along only when recording is on (--obs): the
    # perf-smoke overhead gate compares this mode against the bare run
    prof = _prof.Profiler() if common.TRACE_DIR is not None else None
    _prof.activate(prof)
    try:
        rows = _scaling_rows(sizes, cat, kw, prof)
    finally:
        _prof.activate(None)
    print_table("Fleet-scale planning curve", rows,
                ["n_tasks", "numpy_s", "jax_s", "jax_warmup_s",
                 "jax_compile_s", "incremental_s", "jit_speedup",
                 "incr_speedup", "instances", "fallback"])
    return rows


def _scaling_rows(sizes, cat, kw, prof):
    rows = []
    for n in sizes:
        tasks = array_fleet(n, np.random.default_rng(n))
        dt_np = None
        if n <= NUMPY_CAP:
            t0 = time.time()
            full_reconfiguration(tasks, cat, table=None, engine="numpy", **kw)
            dt_np = time.time() - t0
        # warm up (jit compile + shape-bucket retraces), then time.  The
        # engine's jax_pack spans land on the active profiler; the
        # stage=compile share of the warmup call becomes jax_compile_s.
        n_spans = len(prof.spans) if prof is not None else 0
        t0 = time.time()
        full_reconfiguration(tasks, cat, table=None, engine="jax", **kw)
        dt_warm = time.time() - t0
        dt_compile = (sum(s.duration_s for s in prof.spans[n_spans:]
                          if s.tags.get("stage") == "compile")
                      if prof is not None else None)
        t0 = time.time()
        cfg = full_reconfiguration(tasks, cat, table=None, engine="jax", **kw)
        dt_jx = time.time() - t0
        # single-instance disturbance: evacuate the first instance and repack
        # only its tasks (the dirty set) instead of re-planning the fleet.
        live = [LiveInstance(i, k, tuple(tids))
                for i, (k, tids) in enumerate(cfg.assignments)]
        evac = [live[0].instance_id]
        incremental_reconfiguration(tasks, live, set(), set(), cat, None,
                                    evacuate=evac, engine="jax", **kw)
        t0 = time.time()
        _, fb = incremental_reconfiguration(tasks, live, set(), set(), cat,
                                            None, evacuate=evac, engine="jax",
                                            **kw)
        dt_inc = time.time() - t0
        rows.append({"n_tasks": n,
                     "numpy_s": round(dt_np, 3) if dt_np is not None else "",
                     "jax_s": round(dt_jx, 4),
                     "jax_warmup_s": round(dt_warm, 3),
                     "jax_compile_s": (round(dt_compile, 3)
                                       if dt_compile is not None else ""),
                     "incremental_s": round(dt_inc, 4),
                     "jit_speedup": (round(dt_np / dt_jx, 1)
                                     if dt_np is not None else ""),
                     "incr_speedup": round(dt_jx / max(dt_inc, 1e-9), 1),
                     "instances": len(cfg.assignments),
                     "fallback": fb or ""})
    return rows


def run(quick=False):
    out = {"table4": table4(quick=quick), "table5": table5(quick=quick),
           "scaling": scaling_curve(quick=quick)}
    save_results("bench_micro", out)
    return out


if __name__ == "__main__":
    run()
