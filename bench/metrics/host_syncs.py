"""Blocking device-to-host reads per aggregation, from the program's
``host_syncs.<layer>`` counters (one per array pulled with ``np.asarray``
or ``float``)."""
from bench.program_counters import per_agg

LAYER = "transfer"
SOURCE = "program_counter"
UNIT = "count"
MOVES = "updates_per_s"


def read(w):
    return per_agg(w, "host_syncs")
