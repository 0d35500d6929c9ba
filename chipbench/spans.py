"""Arithmetic on the program's phase spans as a traced run records them.

``rec["spans"]`` holds every span that ``obs.profiler`` closed in the
traced window, in closing order (a child before its parent), each as
``Span.to_dict()`` has it: ``name``, ``start_s``, ``duration_s``,
``parent`` (the index of the enclosing span in the same list, or None),
``round`` (the ordinal of the enclosing ``sched.round``) and ``tags``.  An
untraced run records ``None``.  Every quantity here is per round of the
traced window (``rec["rounds"]``), the denominator of ``sched_host_ms``;
each returns None where the record holds no spans.
"""


def ms_per_round(rec, *names):
    """Summed wall time of the spans named ``names`` (ms/round)."""
    spans = rec.get("spans")
    if spans is None:
        return None
    total = sum(s["duration_s"] for s in spans if s["name"] in names)
    return total / rec["rounds"] * 1e3


def tag_per_round(rec, name, tag):
    """Summed tag ``tag`` of the spans named ``name`` that carry it
    (count/round)."""
    spans = rec.get("spans")
    if spans is None:
        return None
    total = sum(s["tags"][tag] for s in spans
                if s["name"] == name and tag in s["tags"])
    return total / rec["rounds"]


def self_ms_per_round(rec, name):
    """Wall time of the spans named ``name`` less that of their direct
    children: what no inner span covers (ms/round)."""
    spans = rec.get("spans")
    if spans is None:
        return None
    inner = {}
    for s in spans:
        if s["parent"] is not None:
            inner[s["parent"]] = inner.get(s["parent"], 0.0) + s["duration_s"]
    total = sum(s["duration_s"] - inner.get(i, 0.0)
                for i, s in enumerate(spans) if s["name"] == name)
    return total / rec["rounds"] * 1e3
