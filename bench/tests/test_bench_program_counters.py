"""The readers of the program's training sub-spans and transfer counters,
on round records written by a ``repro.obs`` recorder by hand."""
from types import SimpleNamespace

import pytest

from bench import program_counters
from bench.metrics import h2d_mb, host_syncs, train_pull_ms, train_stage_ms
from bench.run import Window, _span_totals


@pytest.fixture
def recorder():
    from repro.obs import RunRecorder, clear_profiler, set_profiler

    rec = RunRecorder()
    set_profiler(rec)
    yield rec
    clear_profiler()


def _aggregation(rec, stage, h2d, syncs, counted=True):
    with rec.span(stage):
        with rec.span("train.gather"):
            pass
        with rec.span("executor.vmapped"):
            for sub in ("train.pack", "train.upload", "train.pull"):
                with rec.span(sub):
                    pass
    if counted:
        rec.metrics.count("h2d_bytes.train", h2d)
        rec.metrics.count("h2d_bytes.evaluate", 1_000_000)
        rec.metrics.count("host_syncs.train", syncs)
        rec.metrics.count("host_syncs.evaluate", 40)
    rec.metrics.count("failures", 0)
    rec.flush_round(round=len(rec.records), mode="sync", host_time_s=0.1)


def _window(rec, n_aggs):
    # the harness's own reduction of the window's records
    spans = _span_totals(rec.records[-n_aggs:])
    w = Window.__new__(Window)
    w.n_aggs, w.spans = n_aggs, spans
    return w


def test_readers_per_aggregation(recorder):
    _aggregation(recorder, "probe", 9_000_000, 3)      # warm-up
    for stage in ("probe", "dispatch"):
        _aggregation(recorder, stage, 2_000_000, 7)
    w = _window(recorder, 2)
    assert h2d_mb.read(w) == pytest.approx(3.0)
    assert host_syncs.read(w) == pytest.approx(47.0)
    stage = [v for p, v in w.spans.items()
             if p.endswith(("/train.gather", "/train.pack"))]
    assert len(stage) == 4
    assert train_stage_ms.read(w) == pytest.approx(1e3 * sum(stage) / 2)
    pull = [v for p, v in w.spans.items() if p.endswith("/train.pull")]
    assert train_pull_ms.read(w) == pytest.approx(1e3 * sum(pull) / 2)


def test_counters_refused_when_the_records_are_not_the_window(recorder):
    for _ in range(3):
        _aggregation(recorder, "complete", 2_000_000, 7)
    w = _window(recorder, 2)
    assert program_counters.window_counters(w) is not None
    # one span total off by a nanosecond, or one aggregation too many
    off = dict(w.spans)
    path = next(iter(off))
    off[path] += 1e-9
    assert program_counters.window_counters(
        SimpleNamespace(n_aggs=2, spans=off)) is None
    assert program_counters.window_counters(
        SimpleNamespace(n_aggs=3, spans=w.spans)) is None
    assert h2d_mb.read(SimpleNamespace(n_aggs=2, spans=off)) is None


def test_a_program_without_the_counters_reads_nothing(recorder):
    # the records a program without sub-spans or transfer counters writes
    for _ in range(2):
        with recorder.span("complete"):
            pass
        recorder.metrics.count("failures", 0)
        recorder.flush_round(round=0, mode="sync", host_time_s=0.1)
    w = _window(recorder, 2)
    assert h2d_mb.read(w) is None and host_syncs.read(w) is None
    assert train_stage_ms.read(w) is None and train_pull_ms.read(w) is None


def test_no_active_recorder_reads_nothing():
    from repro.obs import clear_profiler

    clear_profiler()
    w = SimpleNamespace(n_aggs=2, spans={"probe": 0.1})
    assert h2d_mb.read(w) is None and host_syncs.read(w) is None
