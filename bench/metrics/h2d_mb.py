"""Megabytes per aggregation that cross host to device, from the program's
``h2d_bytes.<layer>`` counters (training inputs and stacked inits,
evaluation rows, parameters that are host arrays where a device
computation takes them, selection features)."""
from bench.program_counters import per_agg

LAYER = "transfer"
SOURCE = "program_counter"
UNIT = "MB"
MOVES = "updates_per_s"


def read(w):
    n = per_agg(w, "h2d_bytes")
    return None if n is None else n / 1e6
