"""Host milliseconds per aggregation staging client training on the host:
every ``train.gather`` span (the clients' rows, ``build_requests``) and
every ``train.pack`` span (padding, shuffle orders and stacking in the
vmapped executor), wherever they nest (``probe``, ``complete``,
``dispatch``)."""
LAYER = "client training"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "updates_per_s"

STAGES = ("train.gather", "train.pack")


def read(w):
    return w.span_ms_per_agg(*[p for p in w.spans
                               if p.rsplit("/", 1)[-1] in STAGES])
