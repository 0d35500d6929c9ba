"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--full] [--only NAME]
        [--obs] [--trace-dir DIR] [--results-dir DIR] [--json PATH]

Emits CSV-style tables to stdout, greppable ``[bench] event key=value``
progress lines, and JSON artifacts under results/.  With ``--obs`` every
simulated run attaches a flight recorder and saves its JSONL trace under
``results/traces/`` (or ``--trace-dir``) for offline replay with
``tools/explain.py``.
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (CI-scale)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale end-to-end (6,274 jobs)")
    ap.add_argument("--only", default=None,
                    help="run a single bench: micro|endtoend|multitask|"
                         "interference|migration|composition|arrival|"
                         "roofline|spot|multiregion|credits|autoscale|"
                         "stability|serving|portfolio|sim")
    ap.add_argument("--obs", action="store_true",
                    help="attach a flight recorder to every simulated run "
                         "and save JSONL traces (tools/explain.py replays "
                         "them)")
    ap.add_argument("--trace-dir", default=None,
                    help="trace output dir (implies --obs; default "
                         "results/traces)")
    ap.add_argument("--results-dir", default=None,
                    help="override the results/ artifact directory (the "
                         "perf-overhead gate writes recording-on results "
                         "to a separate dir)")
    ap.add_argument("--json", default=None,
                    help="write the run report (per-bench timings) as JSON")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    from repro.obs import Reporter

    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from . import (bench_arrival, bench_autoscale, bench_composition,
                   bench_credits, bench_endtoend, bench_interference,
                   bench_micro, bench_migration, bench_multiregion,
                   bench_multitask, bench_portfolio, bench_roofline,
                   bench_serving, bench_sim, bench_spot, bench_stability,
                   common)

    if args.results_dir:
        common.RESULTS_DIR = args.results_dir
    if args.obs or args.trace_dir:
        common.TRACE_DIR = args.trace_dir or os.path.join(
            common.RESULTS_DIR, "traces")
        os.makedirs(common.TRACE_DIR, exist_ok=True)

    benches = {
        "micro": lambda: bench_micro.run(quick=args.quick),
        "endtoend": lambda: bench_endtoend.run(quick=args.quick,
                                               full=args.full),
        "multitask": lambda: bench_multitask.run(quick=args.quick),
        "interference": lambda: bench_interference.run(quick=args.quick),
        "migration": lambda: bench_migration.run(quick=args.quick),
        "composition": lambda: bench_composition.run(quick=args.quick),
        "arrival": lambda: bench_arrival.run(quick=args.quick),
        "roofline": lambda: bench_roofline.run(quick=args.quick),
        "spot": lambda: bench_spot.run(quick=args.quick, full=args.full),
        "multiregion": lambda: bench_multiregion.run(quick=args.quick,
                                                     full=args.full),
        "credits": lambda: bench_credits.run(quick=args.quick,
                                             full=args.full),
        "autoscale": lambda: bench_autoscale.run(quick=args.quick,
                                                 full=args.full),
        "stability": lambda: bench_stability.run(quick=args.quick,
                                                 full=args.full),
        "serving": lambda: bench_serving.run(quick=args.quick,
                                             full=args.full),
        "portfolio": lambda: bench_portfolio.run(quick=args.quick,
                                                 full=args.full),
        "sim": lambda: bench_sim.run(quick=args.quick, full=args.full),
    }
    todo = [args.only] if args.only else list(benches)
    rep = Reporter("bench")
    t0 = time.time()
    for name in todo:
        t1 = time.time()
        rep.emit("start", bench=name)
        benches[name]()
        rep.emit("done", bench=name, wall_s=round(time.time() - t1, 1))
    rep.emit("all_done", benches=len(todo),
             wall_s=round(time.time() - t0, 1),
             trace_dir=common.TRACE_DIR or "")
    if args.json:
        rep.write_json(args.json, quick=args.quick, full=args.full)


if __name__ == "__main__":
    main()
