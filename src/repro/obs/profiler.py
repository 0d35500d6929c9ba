"""Wall-clock span profiler with an inert module-level hook.

``Profiler`` records named spans (start, duration, tags) — plan rounds,
jit warmup vs steady-state execution, simulator sweeps.  Hot paths that
cannot thread a recorder argument (the scheduler round, the planners, the
simulator) call the module-level ``span`` context manager, which is a
shared ``nullcontext`` unless a profiler has been activated with
``activate`` — one attribute read and one ``is None`` branch when off, so
profiling-disabled runs pay nothing measurable.  ``SPANS`` names every span
the program opens.

Spans nest: each records the index (in ``Profiler.spans``) of the span it
opened inside (``parent``) and the ordinal of the enclosing ``sched.round``
(``round``), so a layer's self time and a round's spans can be read back.
Work counts are span tags.  ``Profiler(annotate=True)`` also writes each
span into JAX's profiler trace as a ``TraceAnnotation`` of the same name,
so a device trace attributes its idle gaps to program phases.  The
profiler is wall-clock-only by design: it never touches sim time, RNG or
decisions.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List, Optional

#: every span the program opens, with what it covers
SPANS: Dict[str, str] = {
    "sched.round": "EvaScheduler.schedule, the whole call; tags n_tasks, "
                   "n_pending",
    "sched.policies": "policy-stack hooks: pre_round, plan, keep_bonus, "
                      "evacuate (and drain_mask) before planning; refine "
                      "in _finish",
    "partial.keep_test": "Partial: trim live instances, the round's "
                         "reservation prices, evaluate the instances and "
                         "keep or evict each; tags kept, evicted",
    "partial.best_fit": "Partial: repack tasks into kept instances' spare "
                        "capacity; tags pending, kept, scanned (pending x "
                        "kept pairs tested for capacity), fits (pairs that "
                        "fit), evals (grown-set evaluations)",
    "partial.repack": "Partial: Algorithm 1 over the tasks left to repack",
    "full.candidate": "Full Reconfiguration over every live task",
    "ensemble.saving": "ensemble: S_F and S_P (evaluate_assignments)",
    "ensemble.migration": "ensemble: M_F and M_P (diff_configs, "
                          "migration_cost)",
    "pack.prepare": "Algorithm 1's host inputs: reservation prices, job RP "
                    "sums, pairwise matrix; pack_jax's class collapse and "
                    "padded arrays; tag classes",
    "jax_pack": "pack_jax's device call: upload, device work and the "
                "overflow sync; tags stage, max_fills, n_tasks, and the "
                "call's greedy steps (picks) and fills attempted (fills)",
    "pack.readback": "pack_jax: fill records to the host, expanded to task "
                     "rows; tag records",
    "sim.view": "Simulator: throughput reports and the round's "
                "SchedulerView",
    "sim.execute": "Simulator._execute_config: the adopted plan carried out",
}

#: the span whose ordinal every span inside it carries as ``round``
ROUND = "sched.round"


@dataclasses.dataclass
class Span:
    name: str
    start_s: float           # perf_counter-relative to profiler creation
    duration_s: float = 0.0
    tags: Dict[str, object] = dataclasses.field(default_factory=dict)
    parent: Optional[int] = None  # Profiler.spans index of the enclosing span
    round: Optional[int] = None   # ordinal of the enclosing ``sched.round``

    def to_dict(self) -> dict:
        d = {"name": self.name, "start_s": round(self.start_s, 6),
             "duration_s": round(self.duration_s, 6)}
        if self.tags:
            d["tags"] = self.tags
        if self.parent is not None:
            d["parent"] = self.parent
        if self.round is not None:
            d["round"] = self.round
        return d


class Profiler:
    """Spans are appended as they close, so a child precedes its parent;
    the parent's index is written into its children when it closes."""

    def __init__(self, annotate: bool = False) -> None:
        self._t0 = time.perf_counter()
        self.spans: List[Span] = []
        self._children: List[List[Span]] = []  # one list per open span
        self._rounds = 0
        self._round: Optional[int] = None
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str, **tags) -> Iterator[Span]:
        outer_round = self._round
        if name == ROUND:
            self._round = self._rounds
            self._rounds += 1
        s = Span(name, 0.0, tags=dict(tags), round=self._round)
        children: List[Span] = []
        self._children.append(children)
        note = (_NULL if self._annotation is None
                else self._annotation(name))
        try:
            with note:
                t0 = time.perf_counter()
                s.start_s = t0 - self._t0
                try:
                    yield s
                finally:
                    s.duration_s = time.perf_counter() - t0
        finally:
            self._children.pop()
            self._round = outer_round
            for c in children:
                c.parent = len(self.spans)
            self.spans.append(s)
            if self._children:
                self._children[-1].append(s)

    def totals(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out

    def by_name(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def to_dicts(self) -> List[dict]:
        return [s.to_dict() for s in self.spans]


# --- module-level hook for hot paths that can't thread a profiler ----------
_ACTIVE: Optional[Profiler] = None
_NULL = contextlib.nullcontext()


def activate(profiler: Optional[Profiler]) -> None:
    """Install (or, with ``None``, remove) the process-global profiler."""
    global _ACTIVE
    _ACTIVE = profiler


def active() -> Optional[Profiler]:
    return _ACTIVE


def span(name: str, **tags):
    """Span on the active profiler; a shared no-op context when inactive."""
    if _ACTIVE is None:
        return _NULL
    return _ACTIVE.span(name, **tags)
