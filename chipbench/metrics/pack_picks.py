"""Greedy steps of the jitted pack ``_pack_all_types`` per round: the
``picks`` tag of ``pack_jax``'s ``jax_pack`` spans, summed over the round's
device calls (count/round).  A step adds one task to the instance being
filled or ends that fill.  None where no span carries the tag (a program
that does not count its steps)."""
from chipbench.spans import tag_per_round


def read(rec):
    spans = rec.get("spans")
    if not spans or not any(s["name"] == "jax_pack" and "picks" in s["tags"]
                            for s in spans):
        return None
    return tag_per_round(rec, "jax_pack", "picks")
