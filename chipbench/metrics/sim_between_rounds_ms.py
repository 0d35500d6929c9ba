"""Host time per round between one ``schedule()`` return and the next
entry: the simulator's event loop, accrual and plan execution (ms/round)."""

def read(rec):
    return sum(rec["between_s"]) / rec["rounds"] * 1e3
