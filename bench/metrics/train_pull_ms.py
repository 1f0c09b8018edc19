"""Host milliseconds per aggregation pulling the trained cohort to the
host: every ``train.pull`` span (the stacked params and losses to NumPy).
The bucket step before it is fenced in a traced run, so this is the copy
alone."""
LAYER = "client training"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "updates_per_s"


def read(w):
    return w.span_ms_per_agg(*[p for p in w.spans
                               if p.rsplit("/", 1)[-1] == "train.pull"])
