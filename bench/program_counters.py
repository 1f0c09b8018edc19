"""The program's own counters over the traced window.

``repro.obs`` counts, into each aggregation's round record, the bytes that
cross the host link and the blocking reads (``h2d_bytes.<layer>``,
``d2h_bytes.<layer>``, ``host_syncs.<layer>``).  The records are read from
``repro.obs.active_profiler()``, the recorder the server registered: the
window's aggregations are its last ``w.n_aggs`` round records, which is
checked against the window's own span totals.
"""
from __future__ import annotations


def _span_totals(records) -> dict:
    out = {}
    for rec in records:
        for sp in rec.get("spans", ()):
            out[sp["span"]] = out.get(sp["span"], 0.0) + float(sp["wall_s"])
    return out


def window_counters(w):
    """Counter totals of the window's round records, or ``None`` when the
    recorder holds no such records or their span totals differ from the
    window's in any path or value."""
    from repro.obs import active_profiler

    prof = active_profiler()
    if prof is None or not w.n_aggs:
        return None
    rounds = [r for r in prof.records if r.get("type") == "round"]
    rounds = rounds[-w.n_aggs:]
    if len(rounds) != w.n_aggs or _span_totals(rounds) != w.spans:
        return None
    out = {}
    for r in rounds:
        for name, n in r["metrics"]["counters"].items():
            out[name] = out.get(name, 0) + n
    return out


def per_agg(w, prefix: str):
    """Sum of the counters named ``<prefix>.*`` per aggregation, or
    ``None`` where the program counts none."""
    counters = window_counters(w)
    if counters is None:
        return None
    hit = [n for name, n in counters.items()
           if name.startswith(prefix + ".")]
    return sum(hit) / w.n_aggs if hit else None
