"""Wall time per round of the program's ``jax_pack`` spans: upload, device
work and the host sync of each pack call, overflow retries included
(ms/round)."""

def read(rec):
    if not rec["pack_spans"]:
        return None
    return sum(s["duration_s"] for s in rec["pack_spans"]) / rec["rounds"] * 1e3
