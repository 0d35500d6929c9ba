"""Flight-recorder contract tests.

The subsystem's hard invariant, pinned here: the recorder is a **pure
observer**.  Attaching a ``FlightRecorder`` to a run must leave every
decision — each round's adopted config, the metrics summary, the exact
total cost — bit-identical to the unrecorded run, across every scenario
axis (spot, multi-region, burstable, deferrable, serving, portfolio).

The rest of the file unit-tests the recorder surfaces (event log +
aggregated cost ledger, decision trace, metrics registry + Prometheus
export, wall-clock profiler, JSONL round-trip, structured reporter) and
drives the ``tools/explain.py`` replay CLI end-to-end on a real trace.
"""
import contextlib
import io
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.cluster import (SimConfig, Simulator, burstable_trace,
                           deferrable_trace, physical_trace, portfolio_trace,
                           serving_trace)
from repro.core import (CommitmentModel, EvaScheduler, PriceModel, Provider,
                        aws_catalog, burstable_demo_catalog,
                        dispersed_demo_regions, multi_provider_catalog,
                        multi_region_catalog)
from repro.obs import (EventLog, FlightRecorder, Histogram, MetricsRegistry,
                       Profiler, Reporter, events as EV, profiler as prof_mod)
from repro.policies import (AutoscaleLayer, CreditLayer, MultiRegionLayer,
                            PortfolioLayer, SLOLayer, SpotLayer)

ROOT = Path(__file__).resolve().parent.parent


# -------------------------------------------------- observer-inertness pins
def _spot_pm(seed=7):
    return PriceModel.mean_reverting(discount=0.35, seed=seed)


#: scenario -> (catalog_fn, trace_fn, layers_fn, simcfg_kw); one per demo
#: axis, mirroring the composed scenarios the conservation harness sweeps
SCENARIOS = {
    "spot": (lambda: aws_catalog(price_model=_spot_pm()),
             lambda: physical_trace(n_jobs=8, seed=11,
                                    duration_range_h=(0.3, 0.6)),
             lambda: [SpotLayer()],
             dict(seed=5, preemption_hazard_per_hour=0.5)),
    "multiregion": (lambda: multi_region_catalog(dispersed_demo_regions(3)),
                    lambda: physical_trace(n_jobs=6, seed=11,
                                           duration_range_h=(0.3, 0.6)),
                    lambda: [SpotLayer(), MultiRegionLayer()],
                    dict(seed=5, preemption_hazard_per_hour=0.3)),
    "burstable": (lambda: burstable_demo_catalog(price_model=_spot_pm()),
                  lambda: burstable_trace(n_jobs=8, seed=11),
                  lambda: [SpotLayer(), CreditLayer()],
                  dict(seed=5)),
    "deferrable": (lambda: aws_catalog(price_model=_spot_pm()),
                   lambda: deferrable_trace(n_jobs=10, seed=13),
                   lambda: [SpotLayer(), AutoscaleLayer(strike=0.9)],
                   dict(seed=5, preemption_hazard_per_hour=0.3)),
    "serving": (aws_catalog,
                lambda: serving_trace(n_batch=4, seed=17, horizon_h=2.0,
                                      users=200_000),
                lambda: [SLOLayer()],
                dict(seed=5)),
    "portfolio": (lambda: multi_provider_catalog([
                      Provider(name="aws", price_model=_spot_pm(),
                               commitments=(CommitmentModel(
                                   instance_type="c7i.2xlarge", pool_size=2,
                                   rate_fraction=0.5),)),
                      Provider(name="gcp", cost_scale=1.03,
                               price_model=_spot_pm(seed=9))]),
                  lambda: portfolio_trace(n_steady=2, n_burst=3, seed=23,
                                          horizon_h=2.0),
                  lambda: [SpotLayer(), MultiRegionLayer(),
                           PortfolioLayer()],
                  dict(seed=5, preemption_hazard_per_hour=0.3)),
}


class _Probe(EvaScheduler):
    """Records every round's adopted config for decision-level diffing."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.probe = []

    def schedule(self, view):
        cfg = super().schedule(view)
        self.probe.append((view.time, tuple(cfg.assignments)))
        return cfg


def _run(scenario, recorder):
    catalog_fn, trace_fn, layers_fn, cfg_kw = SCENARIOS[scenario]
    cat = catalog_fn()
    jobs = trace_fn()
    # task/job ids come from global counters: normalize to ranks so the
    # two runs (fresh traces each) compare decision-for-decision
    rank = {t.task_id: i for i, t in enumerate(
        sorted((t for j in jobs for t in j.tasks), key=lambda t: t.task_id))}
    sched = _Probe(cat, policies=layers_fn(), recorder=recorder)
    m = Simulator(cat, jobs, sched, SimConfig(**cfg_kw),
                  recorder=recorder).run()
    trace = [(t, tuple((k, tuple(rank[tid] for tid in tids))
                       for k, tids in assignments))
             for t, assignments in sched.probe]
    return trace, m.summary(), m.total_cost, m


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_recording_is_decision_identical(scenario):
    tr_off, sum_off, cost_off, _ = _run(scenario, recorder=None)
    rec = FlightRecorder(meta={"scenario": scenario})
    tr_on, sum_on, cost_on, m = _run(scenario, recorder=rec)
    assert tr_on == tr_off          # every round's adopted config matches
    assert sum_on == sum_off        # full metrics summary, key for key
    assert cost_on == cost_off      # bit-for-bit, not rounded
    # and the recorder actually observed the run it rode along on
    assert len(rec.events) > 0
    assert len(rec.decisions) == len(tr_on)
    assert rec.events.total_cost() == pytest.approx(cost_on, rel=1e-9,
                                                    abs=1e-9)
    assert m.events is rec.events   # exposed on Metrics for callers
    assert "events" not in sum_on   # ...but never leaks into summary()
    # round events and decision records index the same rounds
    rounds = rec.events.of_kind(EV.ROUND)
    assert [e.get("round_index") for e in rounds] == \
        [d.round_index for d in rec.decisions]


def test_decision_trace_explains_keep_test():
    """Keep tables carry the margin decomposition on a recorded spot run."""
    rec = FlightRecorder()
    _run("spot", recorder=rec)
    entries = [e for d in rec.decisions for e in d.keep_table]
    assert entries, "keep tables never populated"
    for e in entries:
        assert e.margin == pytest.approx(e.saving - (e.cost - e.bonus))
        assert e.bonus == pytest.approx(sum(e.bonus_by_layer.values())
                                        if e.bonus_by_layer else 0.0)
    # spot pressure forces partial rounds; their context is recorded
    forced = [d for d in rec.decisions if d.kind == "forced-partial"]
    assert forced and all(d.evacuated for d in forced)


# ------------------------------------------------------------ event log
def test_event_log_queries_and_ledger():
    log = EventLog()
    log.emit(0.0, EV.PROVISION, instance_id=1, type="m5.large")
    log.emit(5.0, EV.PLACE, instance_id=1, job_id=3, task_id=7)
    log.emit(9.0, EV.PRESSURE, signal="spot", ids=(1, 2))
    log.emit(10.0, EV.TERMINATE, instance_id=1, reason="idle")
    log.record_cost(EV.COST_INSTANCE, "m5.large", 1.5)
    log.record_cost(EV.COST_INSTANCE, "m5.large", 0.5)
    log.record_cost(EV.COST_EGRESS, "region-0", 0.25)
    assert len(log) == 4
    assert [e.kind for e in log.of_kind(EV.PROVISION, EV.TERMINATE)] == \
        [EV.PROVISION, EV.TERMINATE]
    # for_instance includes pressure signals whose id payload names it
    assert [e.kind for e in log.for_instance(1)] == \
        [EV.PROVISION, EV.PLACE, EV.PRESSURE, EV.TERMINATE]
    assert [e.kind for e in log.for_instance(2)] == [EV.PRESSURE]
    assert [e.t for e in log.between(4.0, 9.0)] == [5.0, 9.0]
    assert log.counts()[EV.PROVISION] == 1
    # the ledger aggregates micro-charges into per-cell running sums
    assert log.costs[(EV.COST_INSTANCE, "m5.large")] == pytest.approx(2.0)
    assert log.cost_entries == 3
    assert log.total_cost() == pytest.approx(2.25)
    assert log.cost_by("category") == pytest.approx(
        {"instance": 2.0, "egress": 0.25})
    assert log.cost_by("key") == pytest.approx(
        {"m5.large": 2.0, "region-0": 0.25})


# ------------------------------------------------------- metrics registry
def test_metrics_registry_roundtrip_and_prom():
    reg = MetricsRegistry(maxlen=3)
    reg.inc("rounds")
    reg.inc("rounds", 2)
    for t in range(5):  # overflows the ring buffer: dropped is explicit
        reg.sample("cost_total", float(t), t * 1.5)
    reg.sample("cost_region:us-east", 1.0, 9.25)
    reg.observe("pack_ms", 0.05)
    reg.observe("pack_ms", 50.0)
    assert reg.counters["rounds"] == 3
    assert reg.gauges["cost_total"].dropped == 2
    assert reg.gauges["cost_total"].values() == [3.0, 4.5, 6.0]
    text = reg.prom_text()
    assert "rounds 3" in text
    assert 'cost_region{key="us-east"} 9.25' in text
    assert 'pack_ms_bucket{le="0.1"} 1' in text
    assert "pack_ms_count 2" in text
    back = MetricsRegistry.from_dict(
        json.loads(json.dumps(reg.to_dict())))
    assert back.prom_text() == text
    assert back.gauges["cost_total"].dropped == 2


def test_histogram_cumulative_buckets():
    h = Histogram(buckets=(1.0, 10.0, float("inf")))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.cumulative() == [1, 2, 4]
    assert h.total == 4 and h.sum == pytest.approx(555.5)


# ------------------------------------------------------------- profiler
def test_profiler_spans_and_module_hook():
    p = Profiler()
    with p.span("outer", stage="a"):
        with p.span("inner"):
            pass
    assert [s.name for s in p.spans] == ["inner", "outer"]
    assert p.totals()["outer"] >= p.totals()["inner"] >= 0.0
    assert p.by_name("outer")[0].tags == {"stage": "a"}
    # module hook: inert (shared nullcontext) unless activated
    assert prof_mod.active() is None
    with prof_mod.span("nope") as s:
        assert s is None
    prof_mod.activate(p)
    try:
        with prof_mod.span("hooked") as s:
            assert s is not None
    finally:
        prof_mod.activate(None)
    assert p.by_name("hooked")


def test_profiler_links_parent_and_round():
    p = Profiler()
    with p.span("sim.view"):
        pass
    for _ in range(2):
        with p.span("sched.round"):
            with p.span("full.candidate"):
                with p.span("jax_pack"):
                    pass
            with p.span("ensemble.saving"):
                pass
    by = {(s.name, s.round): i for i, s in enumerate(p.spans)}
    assert p.spans[by[("sim.view", None)]].parent is None
    for r in (0, 1):
        top = by[("sched.round", r)]
        assert p.spans[top].parent is None
        assert p.spans[by[("full.candidate", r)]].parent == top
        assert p.spans[by[("ensemble.saving", r)]].parent == top
        assert p.spans[by[("jax_pack", r)]].parent == \
            by[("full.candidate", r)]
    assert len(by) == len(p.spans) == 9  # every span once, rounds 0 and 1
    inner, outer = p.spans[by[("jax_pack", 0)]], p.spans[by[("sched.round", 0)]]
    assert outer.start_s <= inner.start_s
    assert inner.start_s + inner.duration_s <= \
        outer.start_s + outer.duration_s + 1e-9


def test_profiler_tags_given_and_set():
    p = Profiler()
    prof_mod.activate(p)
    try:
        with prof_mod.span("partial.best_fit", pending=3) as sp:
            pass
        if sp is not None:
            sp.tags["evals"] = 7
    finally:
        prof_mod.activate(None)
    assert p.spans[0].tags == {"pending": 3, "evals": 7}
    d = p.spans[0].to_dict()
    assert d["tags"] == {"pending": 3, "evals": 7} and "parent" not in d


def test_profiler_annotates_only_when_asked(monkeypatch):
    # annotate=False: jax.profiler is neither imported nor called
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    p = Profiler()
    with p.span("sched.round"):
        with p.span("jax_pack"):
            pass
    assert [s.name for s in p.spans] == ["jax_pack", "sched.round"]
    with pytest.raises(ImportError):
        Profiler(annotate=True)
    # annotate=True: one TraceAnnotation of the span's name around each span
    written = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            written.append(("enter", self.name))

        def __exit__(self, *exc):
            written.append(("exit", self.name))

    fake = type(sys)("jax.profiler")
    fake.TraceAnnotation = Note
    monkeypatch.setitem(sys.modules, "jax.profiler", fake)
    p = Profiler(annotate=True)
    with p.span("sched.round"):
        with p.span("jax_pack"):
            pass
    assert written == [("enter", "sched.round"), ("enter", "jax_pack"),
                       ("exit", "jax_pack"), ("exit", "sched.round")]


def test_profiler_subclass_wrapping_span_keeps_links():
    # a subclass that wraps ``span`` (as a caller writing its own trace
    # annotations does) still gets parent and round links
    seen = []

    class Wrapped(Profiler):
        @contextlib.contextmanager
        def span(self, name, **tags):
            seen.append(name)
            with super().span(name, **tags) as s:
                yield s

    p = Wrapped()
    prof_mod.activate(p)
    try:
        with prof_mod.span("sched.round"):
            with prof_mod.span("full.candidate"):
                with prof_mod.span("jax_pack"):
                    pass
    finally:
        prof_mod.activate(None)
    assert seen == ["sched.round", "full.candidate", "jax_pack"]
    assert [(s.name, s.parent, s.round) for s in p.spans] == [
        ("jax_pack", 1, 0), ("full.candidate", 2, 0),
        ("sched.round", None, 0)]


@pytest.mark.parametrize("annotate", [False, True])
def test_profiler_span_closed_by_an_exception(annotate, monkeypatch):
    open_notes = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            open_notes.append(self.name)

        def __exit__(self, *exc):
            assert open_notes.pop() == self.name

    fake = type(sys)("jax.profiler")
    fake.TraceAnnotation = Note
    monkeypatch.setitem(sys.modules, "jax.profiler", fake)
    p = Profiler(annotate=annotate)
    with pytest.raises(ValueError):
        with p.span("sched.round"):
            with p.span("partial.best_fit"):
                raise ValueError
    assert not open_notes  # every annotation closed with its span
    with p.span("sim.execute"):
        pass
    with p.span("sched.round"):
        pass
    assert [(s.name, s.parent, s.round) for s in p.spans] == [
        ("partial.best_fit", 1, 0), ("sched.round", None, 0),
        ("sim.execute", None, None), ("sched.round", None, 1)]
    assert all(s.duration_s >= 0.0 for s in p.spans)


def test_pack_jax_reads_the_jit_cache_only_when_profiled(monkeypatch):
    from repro.core import TaskSet, engine_jax, full_reconfiguration, make_task
    jitted = engine_jax._pack_all_types
    reads = []

    class Counted:
        def __call__(self, *a, **kw):
            return jitted(*a, **kw)

        def _cache_size(self):
            reads.append(1)
            return jitted._cache_size()

    monkeypatch.setattr(engine_jax, "_pack_all_types", Counted())
    tasks = TaskSet([make_task(job_id=i, workload=i % 3) for i in range(12)])
    cat = aws_catalog()
    off = full_reconfiguration(tasks, cat, engine="jax")
    assert not reads
    p = Profiler()
    prof_mod.activate(p)
    try:
        on = full_reconfiguration(tasks, cat, engine="jax")
    finally:
        prof_mod.activate(None)
    assert reads
    assert on.assignments == off.assignments
    stages = [s.tags["stage"] for s in p.by_name("jax_pack")]
    assert stages and set(stages) <= {"compile", "execute"}


#: the span tree of one scheduler round: span -> the spans it may open in
SPAN_PARENTS = {
    "sched.round": {None}, "sim.view": {None}, "sim.execute": {None},
    "sched.policies": {"sched.round"}, "partial.keep_test": {"sched.round"},
    "partial.best_fit": {"sched.round"}, "partial.repack": {"sched.round"},
    "full.candidate": {"sched.round"}, "ensemble.saving": {"sched.round"},
    "ensemble.migration": {"sched.round"},
    "pack.prepare": {"partial.repack", "full.candidate"},
    "jax_pack": {"partial.repack", "full.candidate"},
    "pack.readback": {"partial.repack", "full.candidate"},
}


def _fleet_run(engine, profiler, monkeypatch):
    import itertools

    from repro.cluster import alibaba_like_trace, traces
    cat = aws_catalog()
    # the same ids in every run: tie-breaks among equal-priced tasks follow
    # set order, which follows the ids' values
    monkeypatch.setattr(traces, "_job_ids", itertools.count(1))
    monkeypatch.setattr(traces, "_task_ids", itertools.count(1_000_000))
    jobs = alibaba_like_trace(n_jobs=40, seed=3, mean_interarrival_s=150.0)
    sched = _Probe(cat, engine=engine)
    prof_mod.activate(profiler)
    try:
        m = Simulator(cat, jobs, sched,
                      SimConfig(seed=5, max_time_s=4 * 3600.0)).run()
    finally:
        prof_mod.activate(None)
    return sched.probe, m.summary(), m.total_cost


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_round_span_tree(engine, monkeypatch):
    p = Profiler()
    trace, _, _ = _fleet_run(engine, p, monkeypatch)
    spans = p.spans
    rounds = [i for i, s in enumerate(spans) if s.name == "sched.round"]
    assert len(rounds) == len(trace) > 0
    seen = set()
    for s in spans:
        parent = None if s.parent is None else spans[s.parent]
        pname = None if parent is None else parent.name
        assert pname in SPAN_PARENTS[s.name], (s.name, pname)
        seen.add(s.name)
        if s.name in ("sim.view", "sim.execute"):
            assert s.round is None
        elif s.name == "sched.round":
            assert s.tags["n_tasks"] >= s.tags["n_pending"] >= 0
        else:  # inside a round: its round's id, within its parent's time
            assert s.round == parent.round is not None
            assert parent.start_s <= s.start_s
            assert s.start_s + s.duration_s <= \
                parent.start_s + parent.duration_s + 1e-9
    assert [spans[i].round for i in rounds] == list(range(len(rounds)))
    want = set(SPAN_PARENTS)
    if engine == "numpy":  # no device call, no records to read back
        want -= {"jax_pack", "pack.readback"}
    assert seen == want
    for i in rounds:
        kids = {s.name for s in spans if s.parent == i}
        assert {"sched.policies", "partial.keep_test", "full.candidate",
                "ensemble.saving", "ensemble.migration"} <= kids
    fits = [s for s in spans if s.name == "partial.best_fit"]
    assert all(s.tags["pending"] >= 1 and s.tags["evals"] >= 0
               for s in fits)
    keeps = p.by_name("partial.keep_test")
    assert all(s.tags["kept"] >= 0 and s.tags["evicted"] >= 0
               for s in keeps)
    if engine == "jax":
        classes = [s.tags["classes"] for s in p.by_name("pack.prepare")
                   if "classes" in s.tags]
        assert classes and min(classes) >= 1
        assert all(s.tags["records"] >= 1 for s in p.by_name("pack.readback"))


@pytest.mark.parametrize("engine,annotate", [("numpy", False),
                                             ("jax", True)])
def test_profiling_is_decision_identical(engine, annotate, monkeypatch):
    off = _fleet_run(engine, None, monkeypatch)
    p = Profiler(annotate=annotate)
    on = _fleet_run(engine, p, monkeypatch)
    assert on[0] == off[0]   # every round's adopted config
    assert on[1] == off[1]   # metrics summary, key for key
    assert on[2] == off[2]   # bit-for-bit cost
    assert p.by_name("sched.round")


def test_every_span_the_program_opens_is_named():
    import re
    opened = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        opened |= set(re.findall(r"""\bspan\(\s*["']([^"']+)["']""",
                                 path.read_text()))
    assert "sched.round" in opened and "jax_pack" in opened
    assert opened <= set(prof_mod.SPANS), opened - set(prof_mod.SPANS)
    assert set(prof_mod.SPANS) <= opened  # no name kept for nothing


# ------------------------------------------------------------- reporter
def test_reporter_lines_and_json(tmp_path):
    buf = io.StringIO()
    rep = Reporter("gate", stream=buf)
    rep.emit("cell", col="jax_s", fresh_s=0.25, ok=True)
    rep.emit("note", msg="two words")
    assert buf.getvalue().splitlines() == [
        "[gate] cell col=jax_s fresh_s=0.25 ok=true",
        '[gate] note msg="two words"',
    ]
    assert rep.of("cell") == [{"event": "cell", "col": "jax_s",
                               "fresh_s": 0.25, "ok": True}]
    out = tmp_path / "rep.json"
    rep.write_json(str(out), verdict="pass")
    data = json.loads(out.read_text())
    assert data["scope"] == "gate" and data["verdict"] == "pass"
    assert len(data["records"]) == 2


# ------------------------------------------------- artifact + explain CLI
def test_flight_recorder_roundtrip_and_explain_cli(tmp_path):
    rec = FlightRecorder(meta={"scenario": "spot"})
    _run("spot", recorder=rec)
    with rec.profiler.span("plan"):
        pass
    path = str(tmp_path / "trace.jsonl")
    rec.save(path)
    back = FlightRecorder.load(path)
    assert back.meta == rec.meta
    assert back.events.events == rec.events.events
    assert back.events.costs == pytest.approx(rec.events.costs)
    assert [d.to_dict() for d in back.decisions] == \
        [d.to_dict() for d in rec.decisions]
    assert back.metrics.prom_text() == rec.metrics.prom_text()
    assert [s.name for s in back.profiler.spans] == \
        [s.name for s in rec.profiler.spans]

    def explain(*args):
        r = subprocess.run(
            [sys.executable, str(ROOT / "tools" / "explain.py"), path,
             *args], capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        return r.stdout
    out = explain("summary")
    assert "meta scenario=spot" in out and "decisions rounds=" in out
    out = explain("cost", "--by", "category")
    assert "category=instance" in out and "total $" in out
    # flagship query: why was this instance terminated?
    term = rec.events.of_kind(EV.TERMINATE)[0]
    out = explain("why-terminated", "--instance", str(term.instance_id))
    assert f"instance {term.instance_id} terminated" in out
    assert f"reason={term.get('reason')}" in out
    out = explain("rounds", "--round", "0")
    assert "round=0" in out
    out = explain("timeline", "--kind", "provision", "--limit", "3")
    assert "kind=provision" in out


def test_recorded_run_profiles_its_rounds(tmp_path):
    rec = FlightRecorder(meta={"scenario": "spot"})
    _run("spot", recorder=rec)
    assert prof_mod.active() is None  # restored after the run
    names = {s.name for s in rec.profiler.spans}
    assert {"sched.round", "sim.view", "sim.execute"} <= names
    path = str(tmp_path / "trace.jsonl")
    rec.save(path)
    back = FlightRecorder.load(path)
    assert [(s.parent, s.round) for s in back.profiler.spans] == \
        [(s.parent, s.round) for s in rec.profiler.spans]
    r = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "explain.py"), path,
         "summary"], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    rounds = len(rec.profiler.by_name("sched.round"))
    assert "span sched.round total_s=" in r.stdout
    assert f"n={rounds}" in r.stdout
    # an active profiler is left in place and gets the spans
    outer = Profiler()
    prof_mod.activate(outer)
    try:
        rec2 = FlightRecorder()
        _run("spot", recorder=rec2)
        assert prof_mod.active() is outer
    finally:
        prof_mod.activate(None)
    assert not rec2.profiler.spans and outer.by_name("sched.round")
