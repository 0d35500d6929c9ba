"""The one traffic generator: a deployment's job stream from ``--seed``.

A configuration file states the trace's shape (the duration model, the GPU
mix of Table 8, which Table-7 workloads host GPU and CPU jobs) and the mean
interarrival; a traffic file states how arrivals are laid out in time.  The
per-job draws are a copy of ``repro.cluster.traces.alibaba_like_trace``'s,
in the same order from the same ``default_rng(seed)`` stream, so for the
same seed the Poisson part draws the same workload, demand and duration per
job (``chipbench/tests/test_generator.py`` holds the two to that).

Three things differ from the source, for a windowed run:

* The run starts in steady state.  At t=0 a backlog of round(lambda*E[D])
  jobs is placed whose remaining durations follow the stationary
  residual-life law of the same duration model: a length-biased duration
  times a uniform fraction.  Its size is fixed, not Poisson.
* The jobs themselves, and their order, come from the configuration's
  ``content_seed``.  ``--seed`` only moves the arrival times: the gaps
  between arrivals are shuffled within blocks of ``gap_block``, so every
  seed carries the same jobs, with the same ids, in the same order, and
  each block ends at the same instant.  Shuffling the jobs as well
  renumbers them, and the planner's tie-breaks then lead each seed's
  cluster along its own path.
* Job and task ids are numbered per call, so two runs build identical
  ids.

A traffic file's ``arrivals`` is ``poisson`` (the times above) or
``wave``: the same jobs and times, then each arrival moved to a seeded
uniform instant in the first ``wave_width_s`` of its ``wave_period_s``,
as work submitted on the hour arrives.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core.cluster_types import Job, Task

FAMILIES = ("p3", "c7i", "r7i")
FIRST_TASK_ID = 1_000_000


def mean_duration_h(shape: dict) -> float:
    """E[D] of the duration model, exactly: the body is log-linear between
    quantile anchors, the tail log-uniform on [last anchor, tail_max_h]."""
    p = np.asarray(shape["anchors_p"], float)
    h = np.asarray(shape["anchors_h"], float)
    a = np.log(h)
    body = float(np.sum(np.diff(p) * np.diff(h) / np.diff(a)))
    lo, hi = h[-1], float(shape["tail_max_h"])
    tail = (1.0 - p[-1]) * (hi - lo) / np.log(hi / lo)
    return body + tail


def sample_duration_h(rng, shape: dict, n: int) -> np.ndarray:
    """Copy of ``traces.sample_alibaba_duration_h`` over the config's
    anchors (same draws in the same order)."""
    p = np.asarray(shape["anchors_p"], float)
    h = np.asarray(shape["anchors_h"], float)
    u = rng.uniform(0, 1, size=n)
    out = np.empty(n)
    body = u < p[-1]
    out[body] = np.exp(np.interp(u[body], p, np.log(h)))
    k = (~body).sum()
    if k:
        out[~body] = np.exp(rng.uniform(np.log(h[-1]),
                                        np.log(shape["tail_max_h"]), size=k))
    return out


class _Ids:
    def __init__(self):
        self.job = 0
        self.task = FIRST_TASK_ID

    def job_id(self) -> int:
        self.job += 1
        return self.job

    def task_id(self) -> int:
        self.task += 1
        return self.task - 1


def _draw_job(rng, shape: dict, ids: _Ids, g: int, arrival: float,
              duration_s: float) -> Job:
    """One job's workload and demand, in ``alibaba_like_trace``'s order."""
    gpu_w, cpu_w = shape["gpu_workloads"], shape["cpu_workloads"]
    if g > 0:
        w = int(rng.choice(gpu_w))
        if rng.uniform() < shape["straddle_share"] and 8 * g < 64:
            cpu = float(rng.integers(8 * g + 1, min(24 * g, 64) + 1))
            ram = float(np.round(rng.uniform(61.0 * g,
                                             min(200.0 * g, 488.0)), 1))
        else:
            cpu = float(rng.integers(1, 8 * g + 1))
            ram = float(np.round(rng.uniform(2.0, 55.0 * g), 1))
    else:
        w = int(rng.choice(cpu_w))
        cpu = float(np.round(np.exp(rng.uniform(0.0, np.log(32.0)))))
        ram = float(np.round(np.exp(rng.uniform(np.log(2.0),
                                                np.log(256.0))), 1))
    n_tasks = 1
    mtf = shape["multi_task_fraction"]
    if mtf > 0 and rng.uniform() < mtf:
        n_tasks = int(rng.choice([2, 4]))
    job_id = ids.job_id()
    job = Job(job_id=job_id, workload=w, arrival_time=arrival,
              duration_s=duration_s, n_tasks=n_tasks)
    d = {f: (float(g), cpu, ram) for f in FAMILIES}
    for _ in range(n_tasks):
        job.tasks.append(Task(ids.task_id(), job_id, w, d))
    return job


def _gpu_draws(rng, shape: dict, n: int) -> np.ndarray:
    gpus, probs = zip(*shape["gpu_mix"])
    return rng.choice(gpus, size=n, p=probs)


def arrivals(seed: int, shape: dict, n_jobs: int, mean_interarrival_s: float,
             ids: _Ids) -> List[Job]:
    """Poisson arrivals: exactly the source's draws for ``seed``."""
    rng = np.random.default_rng(seed)
    durations = sample_duration_h(rng, shape, n_jobs) * 3600.0
    gpu = _gpu_draws(rng, shape, n_jobs)
    t = 0.0
    jobs = []
    for i in range(n_jobs):
        t += rng.exponential(mean_interarrival_s)
        jobs.append(_draw_job(rng, shape, ids, int(gpu[i]), t,
                              float(durations[i])))
    return jobs


def backlog(seed: int, shape: dict, n: int, ids: _Ids) -> List[Job]:
    """``n`` jobs live at t=0, with stationary residual durations: each is a
    length-biased draw of the duration model times U(0, 1)."""
    if n == 0:
        return []
    rng = np.random.default_rng([seed, 1])
    pool = sample_duration_h(rng, shape, max(64 * n, 65536))
    picks = rng.choice(pool.size, size=n, p=pool / pool.sum())
    residual_s = pool[picks] * rng.uniform(0.0, 1.0, size=n) * 3600.0
    gpu = _gpu_draws(rng, shape, n)
    return [_draw_job(rng, shape, ids, int(gpu[i]), 0.0,
                      max(float(residual_s[i]), 1.0)) for i in range(n)]


def backlog_size(config: dict, rate_scale: float = 1.0) -> int:
    """round(lambda * E[D]): the live jobs of the steady state."""
    lam = rate_scale / config["mean_interarrival_s"]
    return int(round(lam * mean_duration_h(config["trace"]) * 3600.0))


def jitter(jobs: List[Job], seed: int, block: int) -> List[Job]:
    """The run's arrival times: in every run of ``block`` consecutive
    arrivals the gaps between them shuffled.  The backlog (t=0), the jobs'
    order and their ids stay."""
    arriving = [j for j in jobs if j.arrival_time > 0.0]
    times = np.array([j.arrival_time for j in arriving])
    gaps = np.diff(times, prepend=0.0)
    rng = np.random.default_rng([seed, 5])
    for lo in range(0, len(gaps), block):
        gaps[lo:lo + block] = rng.permutation(gaps[lo:lo + block])
    for job, t in zip(arriving, np.cumsum(gaps)):
        job.arrival_time = float(t)
    return jobs


def wave(jobs: List[Job], seed: int, period_s: float,
         width_s: float) -> List[Job]:
    """Hourly waves: every arrival moved to a uniform instant in the first
    ``width_s`` of its ``period_s`` (the period its arrival time lies in).
    The backlog (t=0), the jobs, their order and their ids stay."""
    arriving = [j for j in jobs if j.arrival_time > 0.0]
    rng = np.random.default_rng([seed, 6])
    offset = rng.uniform(0.0, width_s, size=len(arriving))
    for job, u in zip(arriving, offset):
        start = np.floor(job.arrival_time / period_s) * period_s
        job.arrival_time = float(start + u)
    return jobs


ARRIVALS = ("poisson", "wave")


def make_jobs(config: dict, traffic: dict, seed: int,
              rate_scale: float = 1.0) -> List[Job]:
    """The whole trace of one run: the steady-state backlog at t=0, then
    ``arrival_jobs`` arrivals.  What the jobs are, and their order, comes
    from the configuration's ``content_seed``; ``seed`` moves their arrival
    times (``jitter``, then for ``wave`` traffic ``wave``).  ``rate_scale``
    < 1 shrinks the rate and the backlog alike (the CPU rehearsal's tiny
    fleet)."""
    kind = traffic["arrivals"]
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrivals kind {kind!r}")
    shape = config["trace"]
    content = config["content_seed"]
    ids = _Ids()
    held = []
    if traffic["backlog"]:
        held = backlog(content, shape, backlog_size(config, rate_scale), ids)
    n_arrivals = config["arrival_jobs"]
    if rate_scale != 1.0:
        n_arrivals = max(8, int(n_arrivals * rate_scale))
    arriving = arrivals(content, shape, n_arrivals,
                        config["mean_interarrival_s"] / rate_scale, ids)
    jobs = jitter(held + arriving, seed, traffic["gap_block"])
    if kind == "wave":
        jobs = wave(jobs, seed, traffic["wave_period_s"],
                    traffic["wave_width_s"])
    return jobs


def task_pool(config: dict, seed: int, n_tasks: int) -> List[Task]:
    """``n_tasks`` tasks with the deployment's demand mix (set-up's pack
    warm-up draws its task sets here)."""
    shape = config["trace"]
    rng = np.random.default_rng([seed, 3])
    ids = _Ids()
    gpu = _gpu_draws(rng, shape, n_tasks)
    out: List[Task] = []
    for i in range(n_tasks):
        out += _draw_job(rng, shape, ids, int(gpu[i]), 0.0, 1.0).tasks
    return out[:n_tasks]
