"""Device time per round of the jitted pack ``_pack_all_types``: its XLA
module events in the trace, summed over the window (ms/round)."""

def read(rec):
    tr = rec["trace"]
    if tr is None or not tr["kernel_calls"]:
        return None
    return tr["kernel_s"] / rec["rounds"] * 1e3
