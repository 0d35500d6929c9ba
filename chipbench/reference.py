"""Plain reference for one scheduling round, independent of the program.

It follows the paper (Eva, arXiv:2503.07437) and imports nothing of
``repro``.  Its inputs are the round's own data: the tasks' demands,
workloads and jobs, the live placements, the throughputs the monitor has
observed so far, and the catalog as the configuration file states it.

* ``Catalog``: the configuration's types; ``at(t)`` prices them at the
  spot market the configuration states (``MeanReverting``), so a round is
  held to the prices of its own time.
* ``reservation_prices``: RP(task) is the hourly cost of the cheapest type
  whose capacity fits the task alone (section 4.2).
* ``Throughput``: an observed co-location set's own entry, else the product
  of pairwise entries, unseen pairs at the default t (section 4.3).
* ``pack``: Algorithm 1 task by task.  Types in descending cost; each fresh
  instance is filled greedily with the candidate that maximises the set's
  TNRP, first row on a tie, until adding lowers it; the instance is kept if
  its TNRP covers its cost.  TNRP of a member of a multi-task job is
  RP - (1 - tput) * (sum of RP over its job) (section 4.4), which is tput*RP
  for a single-task job.  The arithmetic is exact float64.  The
  configuration states float32, whose sums over a fill drift by up to
  ``BAND`` (256 float32 ulps, relative).  Where a fit, the break-even or
  the greedy stop lies within that band of its bar, or two candidates'
  TNRPs within ``CHOICE_BAND`` (16 ulps: both share the fill's sum, so
  only the step's own terms round apart), float32 may rightly decide the
  other way: the pack reports each such decision with the number of
  instances it had made before it, which are final.
* ``instance_tnrp``: the keep test's TNRP of a live set (section 4.5).
* ``saving``, ``migration_cost`` and ``adopt_full``: the ensemble's choice
  between Full and Partial (section 4.5): adopt Full iff
  S_F * D - M_F > S_P * D - M_P, with S the plan's hourly TNRP beyond its
  cost, M the dollars its migrations and launches cost, and D the expected
  time to the next Full reconfiguration.

``dt`` sets the arithmetic's precision: float64 is the reference; the
control computes the same in bfloat16.
"""
from __future__ import annotations

import copy
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EPS = 1e-9
#: relative width of the float32 bands: 256 and 16 float32 ulps
BAND = 256 * 2.0**-23
CHOICE_BAND = 16 * 2.0**-23


class MeanReverting:
    """The configuration's spot market: an Ornstein-Uhlenbeck log price per
    type around ``discount`` times the on-demand cost.  The path is drawn
    once on a grid of ``step_s`` from ``default_rng(seed)``: x_0 = log
    discount, x_i = x_{i-1} + reversion (mu - x_{i-1}) + volatility eps_i,
    one standard normal eps per step and type.  A time takes the grid point
    at or before it (the last one past ``horizon_s``), and the multiplier
    exp(x) is held to [discount / 10, 1]: spot never costs more than on
    demand."""

    def __init__(self, n_types: int, discount: float, volatility: float,
                 reversion: float, step_s: float, horizon_s: float,
                 seed: int):
        self.step_s = float(step_s)
        n_steps = int(horizon_s / step_s) + 1
        mu = np.log(discount)
        eps = np.random.default_rng(seed).standard_normal(
            (n_steps - 1, n_types))
        x = np.empty((n_steps, n_types))
        x[0] = mu
        for i in range(1, n_steps):
            x[i] = (x[i - 1] + reversion * (mu - x[i - 1])
                    + volatility * eps[i - 1])
        self.grid = np.clip(np.exp(x), discount / 10.0, 1.0)

    def multipliers(self, t: float) -> np.ndarray:
        i = min(int(max(t, 0.0) / self.step_s), len(self.grid) - 1)
        return self.grid[i]


#: the market kinds a configuration's ``market`` may name
MARKETS = {"mean_reverting": MeanReverting}


class Catalog:
    """The configuration's instance types: family index, (gpu, cpu, ram)
    capacity and hourly cost of each; with a ``market``, the costs are its
    on-demand base and ``at`` prices them at a time."""

    def __init__(self, types: Sequence[dict], families: Sequence[str],
                 market: Optional[dict] = None):
        self.names = [t["name"] for t in types]
        self.family = np.array([families.index(t["family"]) for t in types])
        self.caps = np.array([t["capacity"] for t in types], float)
        self.costs = np.array([t["hourly_cost"] for t in types], float)
        self.order = np.argsort(-self.costs, kind="stable")
        self.market = None
        if market is not None:
            args = dict(market)
            self.market = MARKETS[args.pop("price_model")](len(types),
                                                           **args)

    def at(self, t: float) -> "Catalog":
        """The catalog at time ``t``'s market prices (itself without a
        market), its types in descending cost again."""
        if self.market is None:
            return self
        snap = copy.copy(self)
        snap.market = None
        snap.costs = self.costs * self.market.multipliers(t)
        snap.order = np.argsort(-snap.costs, kind="stable")
        return snap


def reservation_prices(demand: np.ndarray, cat: Catalog) -> np.ndarray:
    """(T,) cheapest fitting type's cost for each (T, F, R) demand row."""
    fits = np.all(demand[:, cat.family, :] <= cat.caps[None], axis=2)
    return np.where(fits, cat.costs[None], np.inf).min(axis=1)


def job_sums(job_ids: np.ndarray, rp: np.ndarray) -> np.ndarray:
    """(T,) sum of RP over each task's job."""
    out = np.zeros(len(rp))
    for j in np.unique(job_ids):
        sel = job_ids == j
        out[sel] = rp[sel].sum()
    return out


class Throughput:
    """The monitor's observations as the scheduler held them at the round."""

    def __init__(self, entries: Dict[Tuple[int, Tuple[int, ...]], float],
                 n_workloads: int, default: float):
        self.entries = entries
        self.default = default
        self.pairwise = np.full((n_workloads, n_workloads), default)
        for (w, co), v in entries.items():
            if len(co) == 1:
                self.pairwise[w, co[0]] = v

    def lookup(self, w: int, others: Sequence[int]) -> float:
        co = tuple(sorted(int(x) for x in others))
        if not co:
            return 1.0
        if (int(w), co) in self.entries:
            return self.entries[(int(w), co)]
        t = 1.0
        for x in co:
            t *= self.pairwise[w, x]
        return t


def pack(demand: np.ndarray, workloads: np.ndarray, rp: np.ndarray,
         jrp: np.ndarray, cat: Catalog, pairwise: np.ndarray,
         dt=np.float64, close: Optional[list] = None
         ) -> List[Tuple[int, List[int]]]:
    """Algorithm 1 over the rows; returns (type, rows) per instance.  Each
    decision within a float32 band of its bar is appended to ``close`` as
    (kind, type, instances made before it)."""
    one = dt(1.0)
    eps = dt(EPS)
    dem = demand.astype(dt)
    rp_, jr_ = rp.astype(dt), jrp.astype(dt)
    P = pairwise.astype(dt)
    caps, costs = cat.caps.astype(dt), cat.costs.astype(dt)
    band = dt(BAND)
    T = len(rp)
    free = np.ones(T, bool)
    out: List[Tuple[int, List[int]]] = []
    for k in cat.order.tolist():
        d = dem[:, cat.family[k], :]
        while free.any():
            left = caps[k].copy()
            members: List[int] = []
            tput = np.zeros(0, dt)  # members' predicted throughput
            avail = free.copy()
            cur = dt(0.0)
            while True:
                fits = np.all(d <= left + eps, axis=1)
                near = ~fits & np.all(d <= left + eps + band * caps[k],
                                      axis=1)
                cand = np.nonzero(avail & (fits | near))[0]
                if cand.size == 0:
                    break
                wc = workloads[cand]
                if members:
                    wm = workloads[members]
                    grown = tput[:, None] * P[np.ix_(wm, wc)]
                    m_terms = (rp_[members, None]
                               - (one - grown) * jr_[members, None]).sum(0)
                    c_tput = P[np.ix_(wc, wm)].prod(axis=1)
                else:
                    grown = np.zeros((0, cand.size), dt)
                    m_terms = np.zeros(cand.size, dt)
                    c_tput = np.ones(cand.size, dt)
                total = m_terms + rp_[cand] - (one - c_tput) * jr_[cand]
                ok = fits[cand]
                if not ok.any():
                    if close is not None and np.any(total >= cur - eps):
                        close.append(("fit", k, len(out)))
                    break
                b = int(np.argmax(np.where(ok, total, -np.inf)))
                if close is not None:
                    size = max(abs(total[b]), abs(cur), one)
                    rival = np.where(ok, total, -np.inf)
                    rival[b] = -np.inf
                    if np.any(~ok & (total >= total[b] - band * size)):
                        close.append(("fit", k, len(out)))
                    elif np.any((rival != total[b]) & (
                            rival >= total[b] - dt(CHOICE_BAND) * size)):
                        close.append(("choice", k, len(out)))
                    elif abs(total[b] - (cur - eps)) <= band * size:
                        close.append(("stop", k, len(out)))
                if total[b] < cur - eps:
                    break
                r = int(cand[b])
                members.append(r)
                tput = np.concatenate([grown[:, b], [c_tput[b]]]).astype(dt)
                left = left - d[r]
                avail[r] = False
                cur = total[b]
            keep = members and cur >= costs[k] - eps
            if (close is not None and members and not keep
                    and cur >= costs[k] - eps - band * costs[k]):
                close.append(("break-even", k, len(out)))
            if keep:
                out.append((k, members))
                free[members] = False
            else:
                break
    return out


def instance_tnrp(rows: Sequence[int], workloads: np.ndarray, rp: np.ndarray,
                  jrp: np.ndarray, tp: Throughput) -> float:
    """TNRP of a live co-located set, from the observed throughputs."""
    ws = [int(workloads[r]) for r in rows]
    total = 0.0
    for i, r in enumerate(rows):
        t = tp.lookup(ws[i], ws[:i] + ws[i + 1:])
        total += rp[r] - (1.0 - t) * jrp[r]
    return total


def saving(cfg: Sequence[Tuple[int, Sequence[int]]], workloads: np.ndarray,
           rp: np.ndarray, jrp: np.ndarray, tp: Throughput,
           cat: Catalog) -> float:
    """S: the plan's hourly TNRP beyond its instances' cost (rows)."""
    return float(sum(instance_tnrp(rows, workloads, rp, jrp, tp)
                     - cat.costs[k] for k, rows in cfg))


def migration_cost(live: Sequence[Tuple[int, int, Sequence[int]]],
                   cfg: Sequence[Tuple[int, Sequence[int]]],
                   workload_of: Dict[int, int], cat: Catalog,
                   move_delay_s: Sequence[float],
                   start_s: float) -> float:
    """M: the dollars of moving from the live instances (id, type, task
    ids) to the plan (type, task ids).  Each slot of the plan takes the
    live instance of its type with which it shares most tasks (pairs taken
    by overlap, then slot, then instance id), else any live instance of its
    type left over, else a new one, which idles ``start_s`` at its cost.  A
    task that is not on its slot's instance idles its workload's move delay
    at the cost of its new instance and, if it ran on one, of its old."""
    where = {t: (iid, k) for iid, k, ts in live for t in ts}
    on = {iid: set(ts) for iid, _, ts in live}
    overlap: Dict[Tuple[int, int], int] = {}
    for slot, (k, ts) in enumerate(cfg):
        for t in ts:
            if t in where and where[t][1] == k:
                key = (slot, where[t][0])
                overlap[key] = overlap.get(key, 0) + 1
    match: Dict[int, int] = {}
    used = set()
    for _, slot, iid in sorted((-n, s, i) for (s, i), n in overlap.items()):
        if slot not in match and iid not in used:
            match[slot] = iid
            used.add(iid)
    spare: Dict[int, int] = {}
    for iid, k, _ in live:
        if iid not in used:
            spare[k] = spare.get(k, 0) + 1
    cost = 0.0
    for slot, (k, ts) in enumerate(cfg):
        stay = on.get(match.get(slot), set())
        if slot not in match:
            if spare.get(k, 0) > 0:
                spare[k] -= 1
            else:
                cost += start_s / 3600.0 * cat.costs[k]
        for t in ts:
            if t in stay:
                continue
            hourly = cat.costs[k]
            if t in where:
                hourly += cat.costs[where[t][1]]
            cost += move_delay_s[workload_of[t]] / 3600.0 * hourly
    return float(cost)


def adopt_full(s_full: float, m_full: float, s_partial: float,
               m_partial: float, d_hat_s: float) -> Optional[bool]:
    """The ensemble's choice; None where the two values lie within a
    float64 rounding band of each other (the program sums in another
    order)."""
    d = d_hat_s / 3600.0
    v_full, v_partial = s_full * d - m_full, s_partial * d - m_partial
    scale = abs(s_full * d) + abs(s_partial * d) + m_full + m_partial + 1.0
    if abs(v_full - v_partial) <= 1e-9 * scale:
        return None
    return v_full > v_partial
