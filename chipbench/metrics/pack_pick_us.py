"""Device time of one greedy step of the jitted pack ``_pack_all_types``:
its XLA module time in the traced window over the ``picks`` tags of the
``jax_pack`` spans closed in that window (us/pick).  None without a trace,
without the kernel in it, or where no span carries the tag."""


def read(rec):
    tr, spans = rec.get("trace"), rec.get("spans")
    if tr is None or not tr["kernel_calls"] or not spans:
        return None
    picks = sum(s["tags"]["picks"] for s in spans
                if s["name"] == "jax_pack" and "picks" in s["tags"])
    if picks <= 0:
        return None
    return tr["kernel_s"] / picks * 1e6
