"""Structured run observability: spans, metrics, profiling, run records.

The opt-in instrumentation layer for both round engines (``FLConfig
.observe``).  Pieces:

* :mod:`repro.obs.recorder` — span tracing (host wall + virtual clock) and
  the JSONL run record; :data:`NULL_RECORDER` is the zero-overhead,
  RNG-free disabled default.
* :mod:`repro.obs.metrics` — counters / gauges / histograms flushed per
  round (devices online, buffer fill, staleness distribution, per-tier
  lag, adversaries merged, events per window).
* :mod:`repro.obs.profiling` — ``block_until_ready`` timing around
  executor and kernel calls, sub-spans for code below the server,
  host↔device transfer and compile counters, plus the ``jax.profiler``
  trace gate.
* :mod:`repro.obs.manifest` — the reproducibility header (config digest,
  scenario, seed, platform, package versions).
* :mod:`repro.obs.log` — the structured logger behind the engines' round
  lines and stall diagnostics.
* :mod:`repro.obs.report` — run-record reduction (``tools/obs_report.py``).

See docs/observability.md for the span model, metrics catalog and record
schema.
"""
from repro.obs.log import StructuredLogger
from repro.obs.manifest import config_digest, run_manifest
from repro.obs.metrics import NULL_METRICS, MetricsRegistry, NullMetrics
from repro.obs.profiling import (
    active_profiler,
    clear_profiler,
    set_profiler,
    timed_call,
    trace_gate,
)
from repro.obs.recorder import (
    NULL_RECORDER,
    NullRecorder,
    RunRecorder,
    make_recorder,
)

__all__ = [
    "NULL_METRICS",
    "NULL_RECORDER",
    "MetricsRegistry",
    "NullMetrics",
    "NullRecorder",
    "RunRecorder",
    "StructuredLogger",
    "active_profiler",
    "clear_profiler",
    "config_digest",
    "make_recorder",
    "run_manifest",
    "set_profiler",
    "timed_call",
    "trace_gate",
]
