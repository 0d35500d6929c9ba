"""Host time per round inside ``schedule()`` outside the program's
``jax_pack`` spans: the ensemble, Partial's keep test and best fit, class
collapse setup and evaluation (ms/round).  Needs the traced run's spans."""

def read(rec):
    if rec["pack_spans"] is None:
        return None
    packed = sum(s["duration_s"] for s in rec["pack_spans"])
    return (sum(rec["round_s"]) - packed) / rec["rounds"] * 1e3
