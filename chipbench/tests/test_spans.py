"""The span readers, on a span record made by hand, and the record a
traced rehearsal keeps."""
import pytest

import run

MS = 1e-3


def span(name, ms, parent, rnd, **tags):
    return {"name": name, "start_s": 0.0, "duration_s": ms * MS,
            "parent": parent, "round": rnd, "tags": tags}


def spans():
    """Two rounds and the execution of the first's plan, in the order the
    profiler closes them (a child before its parent).  Round 0 keeps, best
    fits and repacks, then builds Full and weighs both; round 1 keeps and
    repacks only."""
    return [
        span("partial.keep_test", 4, 13, 0, kept=3, evicted=1),    # 0
        span("partial.best_fit", 2, 13, 0, evals=5, scanned=12),   # 1
        span("pack.prepare", 0.5, 6, 0),                           # 2
        span("pack.prepare", 1, 6, 0, classes=3),                  # 3
        span("jax_pack", 10, 6, 0, n_tasks=4, max_fills=256),      # 4
        span("pack.readback", 2, 6, 0, records=2),                 # 5
        span("partial.repack", 16, 13, 0),                         # 6
        span("pack.prepare", 1.5, 10, 0),                          # 7
        span("pack.prepare", 3, 10, 0, classes=40),                # 8
        span("jax_pack", 20, 10, 0, n_tasks=41, max_fills=256),    # 9
        span("full.candidate", 30, 13, 0),                         # 10
        span("ensemble.saving", 6, 13, 0),                         # 11
        span("ensemble.migration", 1, 13, 0),                      # 12
        span("sched.round", 64, None, 0, n_tasks=41, n_pending=4),  # 13
        span("sim.execute", 0.75, None, None),                     # 14
        span("partial.keep_test", 3, 21, 1, kept=4, evicted=0),    # 15
        span("pack.prepare", 0.25, 20, 1),                         # 16
        span("pack.prepare", 0.5, 20, 1, classes=2),               # 17
        span("jax_pack", 8, 20, 1, n_tasks=2, max_fills=256),      # 18
        span("pack.readback", 1.25, 20, 1, records=1),             # 19
        span("partial.repack", 11, 21, 1),                         # 20
        span("sched.round", 15, None, 1, n_tasks=43, n_pending=2),  # 21
    ]


def record():
    return {"rounds": 2, "spans": spans()}


NEW = {
    # summed by hand over the two rounds, halved
    "partial_keep_ms": (4 + 3) / 2,
    "partial_best_fit_ms": 2 / 2,
    "partial_fit_evals": 5 / 2,
    "ensemble_eval_ms": (6 + 1) / 2,
    "pack_host_ms": (0.5 + 1 + 2 + 1.5 + 3 + 0.25 + 0.5 + 1.25) / 2,
    "pack_classes": (3 + 40 + 2) / 2,
    # round 0: 64 - (4 + 2 + 16 + 30 + 6 + 1); round 1: 15 - (3 + 11)
    "sched_self_ms": (5 + 1) / 2,
    "sim_execute_ms": 0.75 / 2,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_sums_by_hand(name):
    assert run.reader(name)(record()) == pytest.approx(NEW[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("rec", [{"rounds": 2}, {"rounds": 2, "spans": None}],
                         ids=["no key", "untraced"])
def test_reader_without_spans_reads_nothing(name, rec):
    assert run.reader(name)(rec) is None


def test_self_time_and_direct_children_make_the_round():
    s = spans()
    rounds = [i for i, x in enumerate(s) if x["name"] == "sched.round"]
    children = sum(x["duration_s"] for x in s if x["parent"] in rounds)
    whole = sum(s[i]["duration_s"] for i in rounds)
    self_ms = run.reader("sched_self_ms")(record())
    assert self_ms * 2 * MS + children == pytest.approx(whole, rel=1e-12)


def test_span_check_reads_the_spans_against_the_clock():
    rec = record()
    rec.update(round_s=[0.064, 0.015],
               pack_spans=[{"duration_s": s["duration_s"]}
                           for s in rec["spans"] if s["name"] == "jax_pack"])
    by_spans, by_clock = run.span_check(rec)
    assert by_spans == pytest.approx((64 + 15 - 10 - 20 - 8) / 2, rel=1e-12)
    assert by_clock == pytest.approx(by_spans, rel=1e-12)
    rec["spans"] = rec["spans"][14:]  # round 0's spans lost
    by_spans, by_clock = run.span_check(rec)
    assert abs(by_spans - by_clock) / by_clock > run.SPAN_RTOL


@pytest.mark.parametrize("name", ["fleet1k-steady", "paper-steady"])
def test_traced_rehearsal_keeps_every_span(name):
    """The traced run's record holds every span of its traced rounds, and
    its result line every per-layer metric that reads them."""
    sim = run.simulate(name, 2**34 + 3, 1.5, traced=True,
                       rehearse=run.REHEARSAL_JOBS)
    run.reduce(sim)
    rec = sim["record"]
    names = [s["name"] for s in rec["spans"]]
    assert names.count("sched.round") == rec["rounds"]
    assert names.count("sim.execute") == rec["rounds"]
    assert len(rec["pack_spans"]) == names.count("jax_pack") > 0
    for i, s in enumerate(rec["spans"]):
        assert s["parent"] is None or s["parent"] > i
    got = run.metrics_of(sim["bench"], name, True, rec)
    assert set(NEW) <= set(got)
