"""What decides ``correct``: rounds of the window held to the reference.

While the window runs, the harness keeps, for a sample of rounds drawn
from the seed (``Sampler``), what the round was given (the scheduler's view
and the throughputs it had observed) and what the timed path produced (each
device pack's input rows and placements, and the plan the round adopted).
After the window these rounds are compared with ``reference.py``.  These
numbers come out:

* ``pack_cost_gap``: the largest relative gap between the hourly cost of a
  device pack's placements and that of the reference's Algorithm 1 over the
  same task rows (Full's pack over every live task and Partial's repack).
* ``pack_mismatch_share``: the share of the checked packs' rows whose
  instance (its type and fellow rows) differs from the reference's.
* ``pack_misplaced``: rows a pack places other than exactly once, plus its
  instances over capacity.
* ``plan_violations``: live tasks the adopted plan places other than
  exactly once; a planner given other prices than the reference's for the
  round's time; a plan that is neither of the round's two candidates;
  a Full candidate that is not Full's pack; and in the Partial candidate,
  a live instance kept or evicted against the reference's keep test, a
  kept instance grown by best fit past its capacity or into a set the
  reference finds not cost-efficient, or a tail that is not Partial's
  pack.  A kept instance is not held to its capacity: the simulator leaves
  a task that is still launching where it was, so a live set can exceed it.
  A round in which a policy layer forces instances out (``LAYERS``: spot
  revocation notices) is a forced Partial: it counts one violation if the
  scheduler did not force it, or forced one with nothing to force, and
  otherwise its plan must be Partial's over the surviving instances, with
  no forced-out instance kept; it has no Full candidate and no choice.
* ``ensemble_wrong``: rounds whose adopted candidate is not the one the
  reference's ensemble picks from the two candidates' savings and
  migration costs (``reference.adopt_full``).

Every round is held to its catalog at the round's time
(``reference.Catalog.at``) where a layer plans at market prices: RP, job
RP sums, the keep test, pack costs, S and M.

Where the reference's pack meets a decision within a float32 band of its
bar (``reference.BAND``), the rows of the instances from there on are not
judged for cost gap and mismatch; where the two candidates' values lie
within float64 rounding, the choice is not judged.  Both are counted and
printed beside the numbers.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import reference as ref

#: relative slack of the capacity test: exact decimal fits sum to a few
#: ulps over the capacity in float64
CAP_RTOL = 1e-6
#: keep-test verdicts within this relative margin of the bar are not judged
#: (the program sums the same terms, possibly in another order)
KEEP_RTOL = 1e-9
#: relative slack of the price comparison: the same float64 path, computed
#: apart (a stale grid point is a few percent away)
PRICE_RTOL = 1e-12


def _revoked(view) -> set:
    return set(view.revoked or ())


#: the policy layers the check holds a round to, by the class name in the
#: configuration: whether the layer plans at the round's market prices, and
#: the live instance ids it forces out of the round's plan
LAYERS = {"SpotLayer": (True, _revoked)}


def unknown_layers(config: dict) -> List[str]:
    """The configuration's policy layers the check has no reference for."""
    return [p["layer"] for p in config["scheduler"]["policies"]
            if p["layer"] not in LAYERS]


def round_catalog(cat: ref.Catalog, config: dict, t: float) -> ref.Catalog:
    """The catalog a round at time ``t`` is held to: its market prices
    where a layer plans at them, else the configuration's costs."""
    if any(LAYERS[p["layer"]][0] for p in config["scheduler"]["policies"]):
        return cat.at(t)
    return cat


def evacuated(config: dict, view) -> set:
    """Live instance ids the configuration's layers force out of the
    round's plan."""
    out = set()
    for p in config["scheduler"]["policies"]:
        out |= LAYERS[p["layer"]][1](view)
    return out


@dataclasses.dataclass
class PackCall:
    demand: np.ndarray      # (T, F, R) rows the pack was given
    workloads: np.ndarray   # (T,)
    out: List[Tuple[int, List[int]]]  # (type, rows) per instance
    job_tasks: np.ndarray   # (T,) live tasks of each row's job (job_tasks)


def job_tasks(rp: np.ndarray, job_rp: Optional[np.ndarray]) -> np.ndarray:
    """Live tasks of each pack row's job, from the RP sum over the job that
    the pack was given: a job's tasks share their demand, so the sum is the
    count times the row's own RP.  A pack given no job sums prices each
    task as its own job (1)."""
    jr = rp if job_rp is None else job_rp
    return np.rint(np.asarray(jr) / np.asarray(rp)).astype(np.int64)


Plan = List[Tuple[int, Tuple[int, ...]]]  # (type, task ids) per instance


@dataclasses.dataclass
class RoundRecord:
    view: object            # repro SchedulerView the round was given
    entries: dict           # observed throughputs at the round
    packs: List[PackCall]
    plan: Plan              # the plan the round adopted
    full: Optional[Plan] = None     # the round's two candidates, where seen
    partial: Optional[Plan] = None
    d_hat_s: Optional[float] = None  # the scheduler's D at the round
    forced: bool = False    # the scheduler forced a Partial this round
    #: the hourly costs of the catalog each candidate was planned at
    prices: List[np.ndarray] = dataclasses.field(default_factory=list)


class Sampler:
    """Which window rounds are kept for the check, decided as they come so
    that the window keeps a handful of rounds and not all of them: the
    round with the most tasks, seeded reservoirs of ``n // 2`` rounds that
    repacked and of the rest among all rounds, and one round of each of
    ``STRATA`` that the window has, each taking its slot from the
    all-rounds reservoir:

    * ``pressure``: a round that the scheduler forced, or that holds an
      instance under a revocation notice;
    * ``full``: a round whose Full candidate the ensemble's own savings and
      migration costs rate above Partial's (``reference.adopt_full`` on
      them); in a sound run, a round that adopts Full.  Both are taken from
      what the round reports, not from the verdict, so a scheduler that
      never forces or never adopts Full still has such rounds checked."""

    STRATA = ("pressure", "full")

    def __init__(self, n: int, seed: int):
        self.rng = np.random.default_rng([seed, 4])
        self.rng_strata = np.random.default_rng([seed, 7])
        self.k_rep = n // 2
        self.k_all = max(n - self.k_rep - 1, 0)
        self.rep: List[Tuple[int, RoundRecord]] = []
        self.all: List[Tuple[int, RoundRecord]] = []
        self.seen_rep = self.seen_all = 0
        self.largest: Optional[Tuple[int, int, RoundRecord]] = None
        self.strata = {k: [] for k in self.STRATA}
        self.seen_strata = dict.fromkeys(self.STRATA, 0)

    def _reservoir(self, res, k, seen, item, rng=None) -> None:
        if len(res) < k:
            res.append(item)
        else:
            j = int((rng or self.rng).integers(seen))
            if j < k:
                res[j] = item

    def offer(self, index: int, n_tasks: int, repacked: bool,
              make: Callable[[], RoundRecord],
              strata: Sequence[str] = ()) -> None:
        made = []

        def item():
            if not made:
                made.append((index, make()))
            return made[0]

        self.seen_all += 1
        self._reservoir(self.all, self.k_all, self.seen_all, None)
        if repacked:
            self.seen_rep += 1
            self._reservoir(self.rep, self.k_rep, self.seen_rep, None)
        for k in strata:
            self.seen_strata[k] += 1
            self._reservoir(self.strata[k], 1, self.seen_strata[k], None,
                            self.rng_strata)
        # fill the slots just admitted (the None placeholders)
        for res in (self.all, self.rep, *self.strata.values()):
            for i, x in enumerate(res):
                if x is None:
                    res[i] = item()
        if self.largest is None or n_tasks > self.largest[0]:
            self.largest = (n_tasks,) + item()

    def records(self) -> List[RoundRecord]:
        kept = dict(self.rep)
        if self.largest is not None:
            kept[self.largest[1]] = self.largest[2]
        # a stratum's round not kept already takes one of the slots that
        # the all-rounds reservoir adds, at random among the others
        taken = {i: r for res in self.strata.values() for i, r in res
                 if i not in kept}
        slots = sum(1 for i, _ in self.all if i not in kept)
        others = [(i, r) for i, r in self.all
                  if i not in kept and i not in taken]
        n = min(max(slots - len(taken), 0), len(others))
        pick = self.rng_strata.choice(len(others), n, replace=False)
        kept.update(taken)
        kept.update(others[j] for j in pick.tolist())
        return [kept[i] for i in sorted(kept)]


def _match_rows(call: PackCall, demand: np.ndarray, workloads: np.ndarray,
                sizes: np.ndarray) -> Optional[np.ndarray]:
    """Round rows of a pack call's rows: both are in ascending task id, so
    the call's rows are a subsequence of the round's.  A row matches one of
    the same workload and demand whose job has as many live tasks
    (``call.job_tasks`` against ``sizes``, per round row): rows alike in
    content but of jobs of other sizes carry other job RP sums."""
    if (len(call.workloads) == len(workloads)
            and np.array_equal(call.workloads, workloads)
            and np.array_equal(call.demand, demand)
            and np.array_equal(call.job_tasks, sizes)):
        return np.arange(len(workloads))
    out, j = [], 0
    for i in range(len(call.workloads)):
        while j < len(workloads) and not (
                workloads[j] == call.workloads[i]
                and sizes[j] == call.job_tasks[i]
                and np.array_equal(demand[j], call.demand[i])):
            j += 1
        if j == len(workloads):
            return None
        out.append(j)
        j += 1
    return np.asarray(out, dtype=np.int64)


def _overfull(cat: ref.Catalog, demand: np.ndarray,
              placed: Sequence[Tuple[int, Sequence[int]]]) -> int:
    bad = 0
    for k, rows in placed:
        used = demand[list(rows), cat.family[k], :].sum(axis=0)
        bad += int(np.any(used > cat.caps[k] * (1 + CAP_RTOL)))
    return bad


def _placed_once(n: int, placed: Sequence[Tuple[int, Sequence[int]]]) -> int:
    counts = np.zeros(n, dtype=np.int64)
    for _, rows in placed:
        np.add.at(counts, np.asarray(rows, dtype=np.int64), 1)
    return int(np.count_nonzero(counts != 1))


def _mismatched(rows, got, want) -> int:
    """Of ``rows``, those whose instance (type and fellow rows) differs
    from the reference's."""
    def where(placed):
        at = {}
        for k, rows in placed:
            key = (k, tuple(sorted(rows)))
            for r in rows:
                at[r] = key
        return at
    a, b = where(got), where(want)
    return sum(1 for r in rows if a.get(r) != b.get(r))


def _cost(cat: ref.Catalog, placed) -> float:
    return float(sum(cat.costs[k] for k, _ in placed))


Packer = Callable[..., List[Tuple[int, List[int]]]]


def check_round(rec: RoundRecord, cat: ref.Catalog, config: dict,
                packer: Optional[Packer] = None) -> Dict[str, float]:
    """The numbers of one round, held to ``cat``, the catalog of the
    round's time (``round_catalog``).  ``packer`` replaces the program's
    pack output by its own over the same rows (the control)."""
    sc = config["scheduler"]
    ts = rec.view.tasks
    demand, workloads = ts.demand_by_family, ts.workloads
    ids = ts.ids.tolist()
    row_of = {t: i for i, t in enumerate(ids)}
    rp = ref.reservation_prices(demand, cat)
    jrp = ref.job_sums(ts.job_ids, rp) if sc["multi_task_aware"] else rp
    sizes = np.ones(len(ids), np.int64)  # live tasks of each row's job
    if sc["multi_task_aware"]:
        _, job_of, job_size = np.unique(ts.job_ids, return_inverse=True,
                                        return_counts=True)
        sizes = job_size[job_of]
    aware = sc["interference_aware"]
    tp = ref.Throughput(rec.entries if aware else {}, config["n_workloads"],
                        sc["default_t"] if aware else 1.0)
    out = {"pack_cost_gap": 0.0, "pack_misplaced": 0, "pack_mismatched": 0,
           "rows_judged": 0, "rows_not_judged": 0, "plan_violations": 0,
           "ensemble_wrong": 0, "choices_judged": 0,
           "choices_not_judged": 0, "forced_checked": 0}
    packs = []  # per call: its placements as round rows
    for call in rec.packs:
        rows = _match_rows(call, demand, workloads, sizes)
        if rows is None:
            out["plan_violations"] += 1
            packs.append(None)
            continue
        args = (demand[rows], workloads[rows], rp[rows], jrp[rows], cat,
                tp.pairwise)
        close: list = []
        want = ref.pack(*args, close=close)
        got = call.out if packer is None else packer(*args)
        out["pack_misplaced"] += (_placed_once(len(rows), got)
                                  + _overfull(cat, demand[rows], got))
        packs.append([(k, [int(rows[r]) for r in rr]) for k, rr in got])
        if close:  # the instances made before the first close decision
            want = want[:min(c[2] for c in close)]
        judged = {r for _, rr in want for r in rr}
        if close:
            out["rows_not_judged"] += len(rows) - len(judged)
        if not judged:
            continue
        mine = [(k, rr) for k, rr in got if judged.intersection(rr)]
        out["pack_cost_gap"] = max(
            out["pack_cost_gap"],
            abs(_cost(cat, mine) - _cost(cat, want)) / _cost(cat, want))
        out["pack_mismatched"] += _mismatched(judged, got, want)
        out["rows_judged"] += len(judged)
    if packer is None:
        out["plan_violations"] += sum(
            int(len(c) != len(cat.costs)
                or not np.allclose(c, cat.costs, rtol=PRICE_RTOL, atol=0.0))
            for c in rec.prices)
        _plan_checks(rec, cat, config, demand, workloads, rp, jrp, tp,
                     row_of, packs, out)
    return out


def _plan_checks(rec, cat, config, demand, workloads, rp, jrp, tp, row_of,
                 packs, out) -> None:
    plan = [(k, tuple(t)) for k, t in rec.plan]
    counts = collections.Counter(t for _, tids in plan for t in tids)
    bad = sum(1 for t in row_of if counts.get(t, 0) != 1)
    bad += sum(1 for t in counts if t not in row_of)
    evac = evacuated(config, rec.view)
    bad += int(bool(evac) != rec.forced)
    if bad:
        out["plan_violations"] += bad
        return

    def canon(cfg):
        """Instances by type and their rows' content: a pack call's rows
        are found by content, so of two tasks alike in workload and demand
        either may stand for the other."""
        return collections.Counter(
            (k, tuple(sorted((int(workloads[r]), demand[r].tobytes())
                             for r in rows))) for k, rows in cfg)

    def as_rows(cfg):
        return [(k, [row_of[t] for t in tids]) for k, tids in cfg]

    n_rows = len(row_of)
    full_pack = [p for call, p in zip(rec.packs, packs)
                 if p is not None and len(call.workloads) == n_rows]
    repack = [p for call, p in zip(rec.packs, packs)
              if p is not None and len(call.workloads) != n_rows]
    full, partial = rec.full, rec.partial
    if rec.forced:
        # a forced Partial over the survivors: no Full candidate, no choice
        partial = [(k, tuple(t)) for k, t in partial or plan]
        bad = _partial_violations(rec, cat, demand, workloads, rp, jrp, tp,
                                  row_of, partial, repack, canon, evac)
        out["plan_violations"] += bad + int(plan != partial)
        out["forced_checked"] += 1
        return
    if full is None and partial is None:
        # the candidates were not seen: the plan is judged as the one it is
        if full_pack and canon(as_rows(plan)) == canon(full_pack[-1]):
            full = plan
        else:
            partial = plan
    if full is not None:
        full = [(k, tuple(t)) for k, t in full]
        bad += int(not full_pack
                   or canon(as_rows(full)) != canon(full_pack[-1]))
    if partial is not None:
        partial = [(k, tuple(t)) for k, t in partial]
        bad += _partial_violations(rec, cat, demand, workloads, rp, jrp, tp,
                                   row_of, partial, repack, canon, set())
    adopted_full = plan == full
    if not adopted_full and plan != partial:
        bad += 1
    out["plan_violations"] += bad
    if bad or sorted(full or []) == sorted(partial or []):
        return  # no choice to judge
    if full is None or partial is None or rec.d_hat_s is None:
        out["choices_not_judged"] += 1
        return
    live = [(i.instance_id, i.type_index, tuple(i.task_ids))
            for i in rec.view.live]
    workload_of = {t: int(workloads[r]) for t, r in row_of.items()}
    mig = config["migration"]
    value = []
    for cfg in (full, partial):
        value.append(ref.saving(as_rows(cfg), workloads, rp, jrp, tp, cat))
        value.append(ref.migration_cost(live, cfg, workload_of, cat,
                                        mig["move_delay_s"],
                                        mig["instance_start_s"]))
    pick = ref.adopt_full(*value, rec.d_hat_s)
    if pick is None:
        out["choices_not_judged"] += 1
        return
    out["choices_judged"] += 1
    out["ensemble_wrong"] += int(pick != adopted_full)


def _partial_violations(rec, cat, demand, workloads, rp, jrp, tp, row_of,
                        plan, repack, canon, evac) -> int:
    """The Partial candidate against the keep test, best fit and its
    repack: the kept instances in live order, then the repack's pack.  The
    live instances in ``evac`` are forced out: each that the plan keeps
    counts, and the keep test judges the others."""
    bad = 0
    plan_rows = [(k, [row_of[t] for t in tids]) for k, tids in plan]
    tail = repack[0] if repack else []
    head = plan[:len(plan) - len(tail)]
    if canon(plan_rows[len(head):]) != canon(tail):
        return 1
    live = []
    for inst in rec.view.live:
        alive = tuple(t for t in inst.task_ids if t in row_of)
        if not evac or inst.instance_id not in evac:
            live.append((inst, alive))
        elif alive:
            bad += sum(1 for k, tids in head if k == inst.type_index
                       and tids[:len(alive)] == alive)

    def verdict(rows, k) -> Optional[bool]:
        """Reference keep test: None where too close to the bar to judge."""
        s = ref.instance_tnrp(rows, workloads, rp, jrp, tp)
        bar = cat.costs[k] - ref.EPS
        if abs(s - bar) <= KEEP_RTOL * max(cat.costs[k], 1.0):
            return None
        return bool(s >= bar)

    j = 0
    for inst, alive in live:
        if not alive:
            continue
        rows = [row_of[t] for t in alive]
        if (j < len(head) and head[j][0] == inst.type_index
                and head[j][1][:len(alive)] == alive):
            bad += int(verdict(rows, inst.type_index) is False)
            if len(head[j][1]) > len(alive):
                # best fit may only add what fits beside the live set
                grown = [row_of[t] for t in head[j][1]]
                bad += int(verdict(grown, inst.type_index) is False)
                bad += _overfull(cat, demand, [(inst.type_index, grown)])
            j += 1
        else:
            bad += int(verdict(rows, inst.type_index) is True)
    return bad + (len(head) - j)


COUNTS = ("pack_misplaced", "plan_violations", "ensemble_wrong",
          "rows_not_judged", "choices_judged", "choices_not_judged",
          "forced_checked")


def check_rounds(records: Sequence[RoundRecord], cat: ref.Catalog,
                 config: dict, packer: Optional[Packer] = None
                 ) -> Dict[str, float]:
    """Worst gap, summed counts and the mismatch share over the rounds,
    and how many distinct price lists they were held to."""
    out = dict.fromkeys(COUNTS, 0)
    out.update(pack_cost_gap=0.0, packs_checked=0, tasks_checked=0)
    mismatched = judged = 0
    snapshots = set()
    for rec in records:
        at = round_catalog(cat, config, rec.view.time)
        snapshots.add(at.costs.tobytes())
        r = check_round(rec, at, config, packer)
        out["pack_cost_gap"] = max(out["pack_cost_gap"], r["pack_cost_gap"])
        for k in COUNTS:
            out[k] += r[k]
        mismatched += r["pack_mismatched"]
        judged += r["rows_judged"]
        out["packs_checked"] += len(rec.packs)
        out["tasks_checked"] += sum(len(c.workloads) for c in rec.packs)
    out["pack_mismatch_share"] = mismatched / max(judged, 1)
    out["snapshots_checked"] = len(snapshots)
    return out


def bf16_packer(*args):
    """The control: the reference in the precision below float32."""
    import ml_dtypes
    return ref.pack(*args, dt=ml_dtypes.bfloat16)


def verdicts(numbers: Dict[str, float], limits: Dict[str, float]
             ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each compared number beside its limit; correct if none is over."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    return all(numbers[k] <= limits[k] for k in limits), shown
