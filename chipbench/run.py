#!/usr/bin/env python3
"""One run of one benchmark cell on one TPU chip.

    python3 chipbench/run.py --workload fleet1k-steady --seed 7 --seconds 30 --trace 0
    JAX_PLATFORMS=cpu python3 chipbench/run.py --rehearse

A cell (``BENCHMARK.json``'s ``workloads``) is a deployment from
``chipbench/configs/<config>.json`` under the arrival mix of
``chipbench/traffic/<traffic>.json``; ``chipbench/cells/<cell>.json`` holds
how the harness runs it (set-up rounds, warm-up sizes, rounds checked and
the limits of ``correct``).  One run, in this one process:

1. stamps the device and exits non-zero without a TPU (no CPU fallback);
2. keeps JAX's compilation cache in the checkout;
3. builds the trace from ``--seed`` (``generator.py``);
4. warms the cell's pack shapes through ``full_reconfiguration`` on task
   sets of the deployment's demand mix, then runs the program's own
   ``Simulator.run`` loop with ``EvaScheduler`` on the device planner,
   under the deployment's price model and policy layers where it states
   them (``build``);
   the cell's first ``setup_rounds`` rounds place the starting population;
5. opens the window at the next round boundary and closes it at the first
   round boundary ``--seconds`` later;
6. holds a sample of the window's rounds to the reference (``check.py``),
   each at its own time's market prices, and prints one JSON line.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's first ``TRACE_SECONDS`` and the program's phase spans in them
(``obs.profiler``; ``chipbench/spans.py``).  Every metric is computed by
``chipbench/metrics/<name>.py`` from the run's record.  ``--rehearse``
runs every cell at a tiny fleet on the CPU, traced and not, and prints no
result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

# libtpu would log under /tmp/tpu_logs otherwise: a run writes only inside
# its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
TRACE_SECONDS = 10.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
KERNEL = "_pack_all_types"
REHEARSAL_JOBS = 20  # live jobs of a CPU rehearsal's fleet
#: largest relative gap between the host time per round read from the
#: spans and from the host clock (``span_check``)
SPAN_RTOL = 0.03
#: the ``SimConfig`` settings a configuration's ``sim`` may state
SIM_KEYS = {"price_update_interval_s", "preemption_notice_s",
            "preemption_hazard_per_hour", "checkpoint_period_s"}


def _paths() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chipbench: the system under test is missing ({SRC}/repro)")
    sys.path[:0] = [SRC, ROOT]


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        sys.exit(f"chipbench: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "cells", name + ".json")) as f:
        harness = json.load(f)
    return bench, cell, config, traffic, harness


def reader(name: str):
    """``chipbench/metrics/<name>.py``'s ``read(record)``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell: str, traced: bool, rec: dict) -> dict:
    """The cell's metrics that the record yields, each by its reader."""
    out = {}
    for m in bench["per_layer" if traced else "end_to_end"]:
        if cell not in m.get("workloads", [cell]):
            continue
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Compiles:
    """XLA executables built (compiled or read from the persistent cache),
    counted through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.n += 1


def _wrap(orig, wrapper) -> None:
    """Puts ``wrapper`` in ``orig``'s place wherever a ``repro`` module
    holds it, so the program's own calls go through it."""
    for mod in list(sys.modules.values()):
        held = getattr(mod, orig.__name__, None)
        if (getattr(mod, "__name__", "").startswith("repro")
                and callable(held) and inspect.unwrap(held) is orig):
            setattr(mod, orig.__name__, wrapper)


class PackRecorder:
    """While ``calls`` is a list, keeps each device pack's input rows, its
    reservation prices and job RP sums, and its placements (``pack_jax``),
    and the round's two candidate plans: the
    configuration of the scheduler's own ``full_reconfiguration`` and of
    its ``partial_reconfiguration`` (a Full pack inside Partial's repack is
    no candidate), with the hourly costs of the catalog each was given (the
    array itself, not a copy)."""

    def __init__(self):
        from repro.core import (engine_jax, full_reconfiguration,
                                partial_reconfiguration)
        self.calls = None
        self.full = self.partial = None
        self.prices = []
        self.depth = 0
        orig = inspect.unwrap(engine_jax.pack_jax)
        sig = inspect.signature(orig)

        @functools.wraps(orig)
        def pack_jax(*a, **kw):
            out = orig(*a, **kw)
            if self.calls is not None:
                b = sig.bind(*a, **kw).arguments
                self.calls.append((b["demand_by_family"], b["workloads"],
                                   b["rp"], b["job_rp"], out))
            return out

        full_orig = inspect.unwrap(full_reconfiguration)
        part_orig = inspect.unwrap(partial_reconfiguration)
        full_sig = inspect.signature(full_orig)
        part_sig = inspect.signature(part_orig)

        @functools.wraps(full_orig)
        def full(*a, **kw):
            cfg = full_orig(*a, **kw)
            if self.calls is not None and self.depth == 0:
                self.full = list(cfg.assignments)
                self.prices.append(
                    full_sig.bind(*a, **kw).arguments["catalog"].costs)
            return cfg

        @functools.wraps(part_orig)
        def partial(*a, **kw):
            self.depth += 1
            try:
                cfg = part_orig(*a, **kw)
            finally:
                self.depth -= 1
            if self.calls is not None:
                self.partial = list(cfg.assignments)
                self.prices.append(
                    part_sig.bind(*a, **kw).arguments["catalog"].costs)
            return cfg

        _wrap(orig, pack_jax)
        _wrap(full_orig, full)
        _wrap(part_orig, partial)

    def start(self) -> None:
        self.calls, self.full, self.partial, self.prices = [], None, None, []

    def take(self):
        out = self.calls, self.full, self.partial, self.prices
        self.calls = self.full = self.partial = None
        return out


class Window:
    """The scheduler's hook: times every ``schedule()`` call, opens the
    window after ``setup_rounds`` rounds and closes it at the first round
    boundary ``seconds`` later by ending the simulation there.  A traced
    run profiles the window's first rounds, up to the first round boundary
    ``TRACE_SECONDS`` after it opens: writing out a whole window's device
    events would take minutes."""

    def __init__(self, seconds: float, setup_rounds: int, traced: bool,
                 compiles: Compiles, packs: PackRecorder):
        self.seconds = seconds
        self.setup_rounds = setup_rounds
        self.traced = traced
        self.tracing = False   # the profiler is recording
        self.compiles = compiles
        self.packs = packs
        self.sim = None
        self.sched = None
        self.done = 0          # rounds returned, set-up ones included
        self.state = "setup"   # -> "open" -> "closed"
        self.round_s, self.between_s = [], []
        self.round_kinds = []  # "regular" / "forced", per window round
        self.traced_rounds = 0  # the window's first rounds, in the profile
        self.unplaced = 0      # window rounds that left a live task out
        self.sampler = None
        self.profiler = None
        self._sim_note = contextlib.nullcontext()

    def _annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def round(self, view, plan):
        from chipbench.check import PackCall, RoundRecord, job_tasks
        from chipbench.reference import adopt_full
        t0 = time.perf_counter()
        is_open = self.state == "open"
        if is_open:
            self._sim_note.__exit__(None, None, None)
            self.between_s.append(t0 - self.t_ret)
            self.packs.start()
            estimator = getattr(self.sched, "estimator", None)
            d_hat = estimator.d_hat() if estimator is not None else None
            forced_before = self.sched.forced_partials
            decided_before = len(self.sched.decisions)
        n_compiled = self.compiles.n
        with self._annotate("chipbench.schedule"):
            cfg = plan(view)
        t1 = time.perf_counter()
        self.done += 1
        if is_open:
            calls, full, partial, prices = self.packs.take()
            if self.compiles.n > n_compiled:
                print(f"[chipbench] window round {len(self.round_s)} "
                      f"compiled: pack sizes {[len(c[1]) for c in calls]}",
                      file=sys.stderr)
            self.round_s.append(t1 - t0)
            forced = self.sched.forced_partials > forced_before
            self.round_kinds.append("forced" if forced else "regular")
            strata = []
            if forced or view.revoked:
                strata.append("pressure")
            if len(self.sched.decisions) > decided_before:
                d = self.sched.decisions[-1]
                if adopt_full(d.s_full, d.m_full, d.s_partial, d.m_partial,
                              d.d_hat_s):
                    strata.append("full")
            self.unplaced += _unplaced(view, cfg)
            entries = self.sched.table.entries  # not changed by schedule()
            self.sampler.offer(
                len(self.round_s) - 1, len(view.tasks), len(calls) > 1,
                lambda: RoundRecord(view, dict(entries),
                                    [PackCall(d, w, o, job_tasks(rp, jrp))
                                     for d, w, rp, jrp, o in calls],
                                    list(cfg.assignments), full, partial,
                                    d_hat, forced, prices),
                strata)
            if (self.tracing and t1 - self.t_open
                    >= min(TRACE_SECONDS, self.seconds)):
                self._stop_trace()
            if t1 - self.t_open >= self.seconds:
                self._close(t1, view.time)
                return cfg
        elif self.state == "setup" and self.done == self.setup_rounds:
            self._open(view.time)
        if self.state == "open":
            self._sim_note = self._annotate("chipbench.sim")
            self._sim_note.__enter__()
            self.t_ret = time.perf_counter()
        return cfg

    def _open(self, sim_now: float) -> None:
        if self.traced:
            import jax
            from repro.obs import profiler as prof
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # annotations only, no Python calls
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.tracing = True
            self.profiler = _annotated_profiler()
            prof.activate(self.profiler)
            self._win_note = self._annotate("chipbench.window")
            self._win_note.__enter__()
        self.compiles_open = self.compiles.n
        self.sim_open = sim_now
        self.state = "open"
        self.t_open = time.perf_counter()

    def _stop_trace(self) -> None:
        import jax
        from repro.obs import profiler as prof
        self._win_note.__exit__(None, None, None)
        prof.activate(None)
        self.tracing = False
        self.traced_rounds = len(self.round_s)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"[chipbench] trace: {self.traced_rounds} rounds, written in "
              f"{time.perf_counter() - t_stop:.2f} s", file=sys.stderr)

    def _close(self, t1: float, sim_now: float) -> None:
        self.t_close = t1
        self.sim_close = sim_now
        self.compiles_window = self.compiles.n - self.compiles_open
        self.state = "closed"
        self.sim.cfg.max_time_s = sim_now  # the run loop stops here


def _unplaced(view, cfg) -> int:
    """1 if the plan leaves a live task out, or places one twice."""
    import numpy as np
    placed = [t for _, ts in cfg.assignments for t in ts]
    if len(placed) != len(view.tasks):
        return 1
    return int(not np.array_equal(np.sort(np.asarray(placed, np.int64)),
                                  np.sort(view.tasks.ids)))


def _annotated_profiler():
    """``obs.profiler.Profiler`` whose spans also write a profiler
    ``TraceAnnotation``, so the trace shows the program's pack calls."""
    import jax
    from repro.obs.profiler import Profiler

    class Annotated(Profiler):
        @contextlib.contextmanager
        def span(self, name, **tags):
            with jax.profiler.TraceAnnotation(name):
                with super().span(name, **tags) as s:
                    yield s

    return Annotated()


def build(config: dict, traffic: dict, seed: int, window: Window,
          rate_scale: float):
    """The program's simulator and scheduler for this deployment.  Beyond
    the catalog and the scheduler's switches, a configuration may state a
    ``market`` (its price model by name and that model's arguments), the
    spot settings of ``sim`` (``SIM_KEYS``), and ``scheduler.policies``,
    each ``{"layer": <class in repro.policies>, "args": {...}}``."""
    import repro.policies as policies_mod
    from repro.cluster import SimConfig, Simulator
    from repro.core import EvaScheduler, catalog as catalog_mod

    from chipbench import check, generator, reference

    market = dict(config.get("market") or {})
    price_model = []
    if market:
        kind = market.pop("price_model")
        if kind not in reference.MARKETS:
            sys.exit(f"chipbench: no reference for market {kind!r}")
        price_model.append(getattr(catalog_mod.PriceModel, kind)(**market))
    cat = getattr(catalog_mod, config["program_catalog"])(*price_model)
    mine = [(t["name"], t["family"], tuple(t["capacity"]), t["hourly_cost"])
            for t in config["catalog"]]
    theirs = [(t.name, t.family, tuple(t.capacity), t.hourly_cost)
              for t in cat.types]
    if mine != theirs:
        sys.exit("chipbench: the program's catalog differs from the "
                 "configuration's")
    from repro.core import workloads as wl
    mig = config["migration"]
    if (mig["move_delay_s"] != [w.checkpoint_delay_s + w.launch_delay_s
                                for w in wl.WORKLOADS]
            or mig["instance_start_s"] != (wl.INSTANCE_ACQUISITION_S
                                           + wl.INSTANCE_SETUP_S)):
        sys.exit("chipbench: the program's migration delays differ from "
                 "the configuration's")
    sc = config["scheduler"]
    layers = []
    for p in sc["policies"]:
        cls = getattr(policies_mod, p["layer"], None)
        if not (isinstance(cls, type)
                and issubclass(cls, policies_mod.PolicyLayer)):
            sys.exit(f"chipbench: no policy layer {p['layer']!r} in "
                     f"repro.policies")
        layers.append(cls(**p.get("args", {})))
    unjudged = check.unknown_layers(config)
    if unjudged:
        sys.exit(f"chipbench: the check has no reference for the policy "
                 f"layers {unjudged}")
    sim = config.get("sim", {})
    if set(sim) - SIM_KEYS:
        sys.exit(f"chipbench: unknown sim keys {sorted(set(sim) - SIM_KEYS)}")
    kw = {}
    if "engine" in inspect.signature(EvaScheduler.__init__).parameters:
        kw["engine"] = "jax"

    class TimedEva(EvaScheduler):
        def schedule(self, view):
            return window.round(view, super().schedule)

    sched = TimedEva(cat, interference_aware=sc["interference_aware"],
                     multi_task_aware=sc["multi_task_aware"],
                     mode=sc["mode"], default_t=sc["default_t"],
                     **({"policies": layers} if layers else {}), **kw)
    window.sched = sched
    jobs = generator.make_jobs(config, traffic, seed, rate_scale)
    # the cloud's own draws (acquisition and setup delays) come from the
    # content seed too: every run gets the same delays, in its own order
    sim = Simulator(cat, jobs, sched, SimConfig(
        round_interval_s=config["round_interval_s"],
        seed=config["content_seed"], **sim))
    window.sim = sim
    return cat, sched, sim, kw


def warm_up(config: dict, harness: dict, cat, sched, kw: dict, seed: int,
            largest: int) -> None:
    """Compile the pack for the task-set sizes the cell's rounds use."""
    from repro.core import TaskSet, full_reconfiguration

    from chipbench import generator
    sc = config["scheduler"]
    sizes = [n for n in harness["warm_tasks"] if n <= largest]
    pool = generator.task_pool(config, seed, max(sizes))
    for n in sizes:
        full_reconfiguration(TaskSet(pool[:n]), cat, sched.table,
                             interference_aware=sc["interference_aware"],
                             multi_task_aware=sc["multi_task_aware"], **kw)
    warm_record_slices(harness["warm_records"], largest)


def warm_record_slices(ranges, largest: int) -> None:
    """The pack reads its fill records back as ``buffer[:n_records]`` on
    the device, and each new length compiles a slice.  Set-up builds the
    lengths that the cell's rounds produce: for every entry, buffers of
    ``fills`` records (and ``fills`` x classes) cut at each length in
    ``records``."""
    import jax.numpy as jnp
    for r in ranges:
        lo, hi = r["records"]
        if hi > largest:
            continue
        bufs = [jnp.zeros((r["fills"],), jnp.int32)] + [
            jnp.zeros((r["fills"], c), jnp.int32) for c in r["classes"]]
        for n in range(lo, hi + 1):
            for b in bufs:
                b[:n].block_until_ready()


def simulate(name: str, seed: int, seconds: float, traced: bool,
             rehearse: int = 0) -> dict:
    """Set-up and window of one run; returns what the run recorded.  A
    traced run leaves its profile under ``TRACE_DIR`` for ``reduce``.
    ``rehearse`` > 0 runs on the CPU with a fleet of that many live jobs."""
    import jax

    from chipbench import generator

    bench, cell, config, traffic, harness = load_cell(name)
    devs = jax.devices()
    dev = devs[0]
    if not rehearse:
        if dev.platform != "tpu":
            sys.exit(f"chipbench: JAX found no TPU (platform {dev.platform})")
        if len(devs) < cell["chips"]:
            sys.exit(f"chipbench: {cell['chips']} chips wanted, "
                     f"{len(devs)} found")
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache(ROOT)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = None
    if traced and not rehearse:
        with open(os.path.join(HERE, "peaks.json")) as f:
            table = json.load(f)
        if dev.device_kind not in table:
            sys.exit(f"chipbench: no peaks for {dev.device_kind!r}")
        peaks = table[dev.device_kind]

    rate_scale = 1.0
    if rehearse:
        rate_scale = min(1.0, rehearse / max(generator.backlog_size(config), 1))
    from chipbench import check
    compiles = Compiles()
    window = Window(seconds, harness["setup_rounds"], traced, compiles, None)
    window.sampler = check.Sampler(harness["check_rounds"], seed)
    t_dev = time.perf_counter()
    cat, sched, sim, kw = build(config, traffic, seed, window, rate_scale)
    window.packs = PackRecorder()  # after build: every planner module loaded
    t_built = time.perf_counter()
    warm_up(config, harness, cat, sched, kw, seed, 64 if rehearse else 10**9)
    t_warm = time.perf_counter()
    n_warm = compiles.n
    sim.run()
    if window.state != "closed":
        sys.exit(f"chipbench: the trace ended before the window closed "
                 f"({window.done} rounds, state {window.state})")
    print(f"[chipbench] set-up: start to device {t_dev - T_START:.3f} s, "
          f"trace and simulator {t_built - t_dev:.3f} s, warm-up "
          f"{t_warm - t_built:.3f} s ({n_warm} executables), set-up rounds "
          f"{window.t_open - t_warm:.3f} s ("
          f"{window.compiles_open - n_warm} executables)", file=sys.stderr)
    mem = dev.memory_stats() or {}
    record = {
        "cell": name, "config": config,
        "rounds": len(window.round_s), "round_s": window.round_s,
        "round_kinds": window.round_kinds, "between_s": window.between_s,
        "window_s": window.t_close - window.t_open,
        "sim_hours": (window.sim_close - window.sim_open) / 3600.0,
        "setup_s": window.t_open - T_START,
        "compiles_in_window": window.compiles_window,
        "pack_spans": None, "spans": None, "trace": None, "peaks": peaks,
    }
    if traced:
        # the per-layer metrics read the traced rounds alone
        n = window.traced_rounds
        record.update(rounds=n, round_s=window.round_s[:n],
                      round_kinds=window.round_kinds[:n],
                      between_s=window.between_s[:n])
        # every span the profiler closed while the trace ran, in its order
        spans = window.profiler.spans
        record["spans"] = [
            {"name": s.name, "start_s": s.start_s, "duration_s": s.duration_s,
             "parent": s.parent, "round": s.round, "tags": dict(s.tags)}
            for s in spans]
        record["pack_spans"] = [
            {"n_tasks": s.tags.get("n_tasks"), "duration_s": s.duration_s,
             "max_fills": s.tags.get("max_fills")}
            for s in spans if s.name == "jax_pack"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    return {"bench": bench, "config": config, "harness": harness,
            "name": name, "seed": seed,
            "traced": traced, "records": window.sampler.records(),
            "attempted": len(window.round_s), "unplaced": window.unplaced,
            "record": record, "device": device}


def reduce(run: dict):
    """Reads and removes the traced run's profile; returns the breakdown
    (None where the trace holds no device events, as on the CPU)."""
    from chipbench import tracing
    t0 = time.perf_counter()
    written = sum(os.path.getsize(os.path.join(d, f))
                  for d, _, fs in os.walk(TRACE_DIR) for f in fs)
    ev = tracing.load(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if not ev["modules"]:
        return None
    red = tracing.reduce_events(ev, KERNEL)
    print(f"[chipbench] trace: {len(ev['modules'])} device programs, "
          f"{written / 2**20:.1f} MiB, read in "
          f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
    run["record"]["trace"] = red
    run["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
    return {"device_ops": [list(x) for x in red["device_ops"]],
            "idle_gaps": [list(x) for x in red["idle_gaps"]]}


def judge(run: dict, packer=None) -> dict:
    """The compared numbers of the run's sampled rounds; ``packer`` puts
    the control in the program's place."""
    from chipbench import check, reference
    config, records = run["config"], run["records"]
    cat = reference.Catalog(config["catalog"], config["families"],
                            config.get("market"))
    numbers = check.check_rounds(records, cat, config, packer)
    numbers["rounds_checked"] = len(records)
    numbers["unplaced_rounds"] = run["unplaced"]
    return numbers


def span_check(rec: dict):
    """Host time per round outside the device pack, read twice: from the
    spans (each ``sched.round`` less the ``jax_pack`` spans) and from the
    host clock (``sched_host_ms``: ``schedule()``'s wall less the same
    ``jax_pack`` spans).  A span record that lost, doubled or cut short
    rounds reads apart from the clock."""
    from chipbench import spans
    by_spans = (spans.ms_per_round(rec, "sched.round")
                - spans.ms_per_round(rec, "jax_pack"))
    return by_spans, reader("sched_host_ms")(rec)


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             rehearse: int = 0) -> dict:
    from chipbench import check
    run = simulate(name, seed, seconds, traced, rehearse)
    breakdown = reduce(run) if traced else None
    t_check = time.perf_counter()
    numbers = judge(run)
    record = run["record"]
    correct, shown = check.verdicts(numbers, run["harness"]["limits"])
    print(f"[chipbench] checked {numbers['rounds_checked']} rounds, "
          f"{numbers['packs_checked']} packs, {numbers['tasks_checked']} "
          f"task rows ({numbers['rows_not_judged']} not judged: past a "
          f"decision within the float32 band), "
          f"{numbers['choices_judged']} ensemble choices "
          f"({numbers['choices_not_judged']} not judged), "
          f"{numbers['forced_checked']} forced rounds, "
          f"{numbers['snapshots_checked']} price lists in "
          f"{time.perf_counter() - t_check:.2f} s; window "
          f"{run['attempted']} rounds, {record['sim_hours']:.3f} sim h, "
          f"{record['compiles_in_window']} compiles", file=sys.stderr)
    import numpy as np
    q = np.percentile(record["round_s"], [50, 90, 95, 99]) * 1e3
    print(f"[chipbench] round ms p50/p90/p95/p99 {q.tolist()}",
          file=sys.stderr)
    kinds = np.array(record["round_kinds"])
    if np.any(kinds == "forced"):
        for kind in ("regular", "forced"):
            ms = np.asarray(record["round_s"])[kinds == kind] * 1e3
            if ms.size:
                print(f"[chipbench] {kind} rounds {ms.size}, ms p50/p95 "
                      f"{np.percentile(ms, [50, 95]).tolist()}",
                      file=sys.stderr)
    if traced:
        by_spans, by_clock = span_check(record)
        gap = abs(by_spans - by_clock) / by_clock
        print(f"[chipbench] host ms/round outside jax_pack: spans "
              f"{by_spans!r}, host clock {by_clock!r}; gap {gap!r} "
              f"limit {SPAN_RTOL!r}", file=sys.stderr)
        if not gap <= SPAN_RTOL:
            sys.exit("chipbench: the span record disagrees with the host "
                     "clock; no result")
    for k, v in shown.items():
        print(f"[chipbench] check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": run["attempted"],
              "failed": numbers["unplaced_rounds"],
              "metrics": metrics_of(run["bench"], name, traced, record),
              "device": run["device"]}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = shown
    return result


def rehearse() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [c["name"] for c in json.load(f)["workloads"]]
    for name in cells:
        for traced in (False, True):
            r = run_cell(name, seed=2**33 + 5, seconds=2.0, traced=traced,
                         rehearse=REHEARSAL_JOBS)
            if not r["correct"]:
                sys.exit(f"chipbench: rehearsal of {name} is not correct: "
                         f"{r['checks']}")
            print(f"[rehearse] {name} trace={int(traced)}: "
                  f"{r['attempted']} rounds, metrics "
                  f"{sorted(r['metrics'])}", flush=True)
    print("[rehearse] every cell ran; no device result", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="every cell at a tiny fleet on the CPU; no result")
    args = ap.parse_args(argv)
    _paths()
    if args.rehearse:
        rehearse()
        return
    if not args.workload:
        ap.error("--workload is required")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
