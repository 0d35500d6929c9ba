"""Wall time per round of the simulator carrying out the adopted plan
(``sim.execute``, ``Simulator._execute_config``) (ms/round)."""
from chipbench.spans import ms_per_round


def read(rec):
    return ms_per_round(rec, "sim.execute")
