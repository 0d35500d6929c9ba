"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps attributed to what the host was doing.

``load`` reads an ``.xplane.pb`` (JAX's profiler output) into plain event
lists; ``reduce_events`` does the arithmetic on those lists, so the tests
can hold it to hand-made intervals as well as to a trace recorded on the
chip.  Times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

#: host annotations the harness and ``obs.profiler``'s spans write
WINDOW = "chipbench.window"
HOST_SPANS = ("chipbench.schedule", "chipbench.sim", "jax_pack")


def load(trace_dir: str) -> Dict[str, List[Event]]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: the XLA
    programs run on every TPU device plane (``modules``; a program's event
    spans the ops inside it, and a window holds millions of those) and the
    host's annotations."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out: Dict[str, List[Event]] = {"modules": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"] += [(e.name.split("(")[0], e.start_ns,
                                        e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name == WINDOW or e.name in HOST_SPANS]
    return out


def _merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


class _Cover:
    """Finds the shortest host span that covers an instant.  Spans of one
    name do not overlap each other (one thread, one loop)."""

    def __init__(self, host: Sequence[Event]):
        self.by_name: Dict[str, Tuple[List[float], List[Event]]] = {}
        edges = set()
        for ev in sorted(e for e in host if e[0] != WINDOW):
            starts, evs = self.by_name.setdefault(ev[0], ([], []))
            starts.append(ev[1])
            evs.append(ev)
            edges.update(ev[1:])
        self.edges = sorted(edges)

    def split(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """[lo, hi) cut at every host span's edge, each piece named."""
        i = bisect.bisect_right(self.edges, lo)
        j = bisect.bisect_left(self.edges, hi)
        cuts = [lo] + self.edges[i:j] + [hi]
        return [(self.name_at(0.5 * (a + b)), b - a)
                for a, b in zip(cuts, cuts[1:]) if b > a]

    def name_at(self, t: float) -> str:
        best, best_len = "other", float("inf")
        for starts, evs in self.by_name.values():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0:
                n, s, e = evs[i]
                if t < e and e - s < best_len:
                    best, best_len = n, e - s
        return best


def reduce_events(ev: Dict[str, List[Event]], kernel: str,
                  top: int = 10) -> Dict[str, object]:
    """Busy union, idle time by the innermost host span around it, and
    per-program totals, inside the host's ``chipbench.window`` span
    (seconds)."""
    win = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    lo, hi = win[0]
    modules = _clip(ev["modules"], lo, hi)
    busy = _merge([(s, e) for _, s, e in modules])
    busy_ns = sum(e - s for s, e in busy)
    cover = _Cover(_clip(ev["host"], lo, hi))
    gaps: Dict[str, float] = {}
    edge = lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            for name, ns in cover.split(edge, s):
                gaps[name] = gaps.get(name, 0.0) + ns * 1e-9
        edge = max(edge, e)
    by_op: Dict[str, float] = {}
    for n, s, e in modules:
        by_op[n] = by_op.get(n, 0.0) + (e - s) * 1e-9
    kernel_s = sum(e - s for n, s, e in modules if kernel in n) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "kernel_s": kernel_s,
        "kernel_calls": sum(1 for n, _, _ in modules if kernel in n),
        "device_ops": sorted(by_op.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda x: -x[1])[:top],
    }
