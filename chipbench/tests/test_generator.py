"""The traffic generator is tied to the program's trace generator."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import generator
from repro.cluster.traces import alibaba_like_trace

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def describe(job):
    t = job.tasks[0]
    return (job.workload, job.n_tasks, t.workload, t.demands["p3"],
            t.demands["c7i"], t.demands["r7i"], job.duration_s,
            job.arrival_time)


@pytest.mark.parametrize("config", ["eva-alibaba-paper", "eva-alibaba-1k"])
@pytest.mark.parametrize("seed", [0, 7, 2**40 + 3])
def test_poisson_part_draws_what_the_source_draws(config, seed):
    cfg = load(config)
    mine = generator.arrivals(seed, cfg["trace"], 400,
                              cfg["mean_interarrival_s"], generator._Ids())
    theirs = alibaba_like_trace(n_jobs=400, seed=seed,
                                mean_interarrival_s=cfg["mean_interarrival_s"])
    assert [describe(j) for j in mine] == [describe(j) for j in theirs]


def test_mean_duration_is_the_models():
    shape = load("eva-alibaba-1k")["trace"]
    d = generator.sample_duration_h(np.random.default_rng(0), shape, 4_000_000)
    se = d.std() / np.sqrt(d.size)
    assert abs(d.mean() - generator.mean_duration_h(shape)) < 4 * se


@pytest.mark.parametrize("config", ["eva-alibaba-paper", "eva-alibaba-1k"])
def test_backlog_holds_lambda_times_mean_duration(config):
    cfg = load(config)
    want = (3600.0 * generator.mean_duration_h(cfg["trace"])
            / cfg["mean_interarrival_s"])
    for seed in (1, 2, 3):
        jobs = generator.backlog(seed, cfg["trace"],
                                 generator.backlog_size(cfg),
                                 generator._Ids())
        assert abs(len(jobs) - want) <= 0.5
        assert all(j.arrival_time == 0.0 and j.duration_s > 0 for j in jobs)


def test_backlog_residuals_follow_the_length_biased_law():
    """E[residual] = E[D^2] / (2 E[D]) for the stationary residual life."""
    shape = load("eva-alibaba-1k")["trace"]
    rng = np.random.default_rng(5)
    d = generator.sample_duration_h(rng, shape, 4_000_000)
    want = (d ** 2).mean() / (2 * d.mean())
    got = np.concatenate([
        [j.duration_s / 3600.0 for j in
         generator.backlog(s, shape, 4000, generator._Ids())]
        for s in range(6)])
    se = got.std() / np.sqrt(got.size)
    assert abs(got.mean() - want) < 4 * se


def test_ids_restart_per_run():
    cfg = load("eva-alibaba-paper")
    traffic = {"arrivals": "poisson", "backlog": True, "gap_block": 8}
    a = generator.make_jobs(cfg, traffic, 9)
    b = generator.make_jobs(cfg, traffic, 9)
    ids = [(j.job_id, [t.task_id for t in j.tasks]) for j in a]
    assert ids == [(j.job_id, [t.task_id for t in j.tasks]) for j in b]
    assert ids[0][0] == 1 and ids[0][1] == [generator.FIRST_TASK_ID]
    assert [describe(j) for j in a] == [describe(j) for j in b]
    assert all(t.job_id == j.job_id for j in a for t in j.tasks)


def content(job):
    return describe(job)[:-1]  # all but the arrival time


def test_seeds_move_only_the_arrival_times():
    cfg = load("eva-alibaba-1k")
    traffic = {"arrivals": "poisson", "backlog": True, "gap_block": 8}
    a = generator.make_jobs(cfg, traffic, 1)
    b = generator.make_jobs(cfg, traffic, 2**40 + 1)
    n = generator.backlog_size(cfg)
    assert [content(j) for j in a] == [content(j) for j in b]
    assert [j.job_id for j in a] == [j.job_id for j in b]
    ta = np.array([j.arrival_time for j in a[n:]])
    tb = np.array([j.arrival_time for j in b[n:]])
    assert not np.array_equal(ta, tb)
    # every block of 8 arrivals ends at the same instant
    np.testing.assert_allclose(ta[7::8], tb[7::8], rtol=1e-12)
    assert np.all(np.diff(ta) >= 0) and np.all(ta > 0)


def traffic(name):
    with open(os.path.join(os.path.dirname(CONFIGS), "traffic",
                           name + ".json")) as f:
        return json.load(f)


def digest(jobs):
    """Every job's ids, workload, size, arrival, duration and demands."""
    h = hashlib.sha256()
    for j in jobs:
        h.update(np.array([j.job_id, j.workload, j.n_tasks],
                          np.int64).tobytes())
        h.update(np.array([j.arrival_time, j.duration_s],
                          np.float64).tobytes())
        for t in j.tasks:
            h.update(np.array([t.task_id, t.job_id, t.workload],
                              np.int64).tobytes())
            h.update(np.array([t.demands[f] for f in generator.FAMILIES],
                              np.float64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed, want", [
    (0, "cadccc72d8081dc1bee83fba0d0d6d48b3ad5d84ae86b41a54b46330e55630ca"),
    (7, "b903c4b4dc7a08435d73bbd546c9147ef25ec6a597c7648e76d77c406019774d"),
    (2**40 + 3,
     "f3d6470be8f5b2d3874a572905a748e7b9d473be518cb438d9989383340c689b"),
])
def test_steady_is_unchanged_bit_for_bit(seed, want):
    """The digests of ``steady``'s whole trace before the ``wave`` kind
    was added."""
    jobs = generator.make_jobs(load("eva-alibaba-1k"), traffic("steady"),
                               seed)
    assert digest(jobs) == want


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_wave_arrivals_lie_in_the_first_minutes_of_their_hour(seed):
    wave = traffic("wave")
    period, width = wave["wave_period_s"], wave["wave_width_s"]
    jobs = generator.make_jobs(load("eva-alibaba-1k"), wave, seed)
    t = np.array([j.arrival_time for j in jobs if j.arrival_time > 0.0])
    hour = np.floor(t / period)
    assert np.all(t >= hour * period) and np.all(t < hour * period + width)
    # the waves spread over their window: every hour of the trace has one
    assert len(np.unique(hour)) == int(hour.max()) + 1


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_wave_carries_steadys_jobs_from_steadys_hours(seed):
    cfg = load("eva-alibaba-1k")
    wave = traffic("wave")
    steady = generator.make_jobs(cfg, traffic("steady"), seed)
    waved = generator.make_jobs(cfg, wave, seed)
    assert [content(j) for j in waved] == [content(j) for j in steady]
    assert [(j.job_id, [t.task_id for t in j.tasks]) for j in waved] == [
        (j.job_id, [t.task_id for t in j.tasks]) for j in steady]
    period = wave["wave_period_s"]
    for a, b in zip(steady, waved):
        if a.arrival_time == 0.0:  # the backlog stays at t=0
            assert b.arrival_time == 0.0
        else:
            assert (np.floor(b.arrival_time / period)
                    == np.floor(a.arrival_time / period))
