"""Observability layer tests (:mod:`repro.obs`).

Four contracts pinned here:

1. **Bit-parity** — recording NEVER perturbs numerics.  Every golden
   trajectory case (sync/async/hierarchical/attack) re-runs with
   ``observe=True`` and must reproduce its committed digest byte-for-byte;
   the obs layer is RNG-free and control-flow-free by construction, and
   this suite is what keeps it that way.
2. **Span semantics** — nesting renders as ``/``-joined paths, host wall
   and virtual clocks are both captured, and the report reduction
   (coverage, phase table, validity gate) folds them correctly.
3. **Record schema** — the JSONL round-trip (``manifest.json`` +
   ``run.jsonl``) reloads to exactly the in-memory records, and every
   round record carries the documented keys.
4. **Determinism modulo wall-time** — two identical observed runs differ
   only in the documented volatile keys (``wall_s`` / ``host_time_s`` /
   ``host_s`` / ``created_at``).
"""
import io
import json
import os

import pytest

from repro.fl import (
    AsyncStallError,
    FLConfig,
    FLServer,
    build_policy,
)
from repro.fl.async_engine import AsyncRoundEngine
from repro.obs import (
    MetricsRegistry,
    NULL_RECORDER,
    RunRecorder,
    StructuredLogger,
    active_profiler,
    clear_profiler,
    config_digest,
    make_recorder,
    run_manifest,
    set_profiler,
    timed_call,
)
from repro.obs.report import (
    ROUND_KEYS,
    check_run,
    coverage,
    load_run,
    op_table,
    phase_table,
    render,
)
from test_golden_trajectories import ATTACK_CASES, CASES, _run_case

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

# keys whose values legitimately vary between identical runs (host clocks)
VOLATILE_KEYS = {"wall_s", "host_time_s", "host_s", "created_at"}


@pytest.fixture(autouse=True)
def _no_profiler_leak():
    """Servers with observability enabled register a module-global profiler
    (repro.obs.profiling); clear it after every test so kernel calls in the
    rest of the suite stay unfenced passthroughs."""
    yield
    clear_profiler()


def _scrub(value):
    """Drop the documented wall-clock-varying keys, recursively."""
    if isinstance(value, dict):
        return {k: _scrub(v) for k, v in value.items()
                if k not in VOLATILE_KEYS}
    if isinstance(value, list):
        return [_scrub(v) for v in value]
    return value


# ---------------------------------------------------------------------------
# 1. bit-parity: observe=True reproduces every committed golden digest
# ---------------------------------------------------------------------------
ALL_GOLDEN = ([(s, m, p, "fedavg", 3) for s, m, p in CASES]
              + [(s, m, "fedavg", a, 5) for s, m, a in ATTACK_CASES])


@pytest.mark.parametrize(
    "scenario,mode,policy,aggregator,k", ALL_GOLDEN,
    ids=[f"{s}-{m}-{p if a == 'fedavg' else a}"
         for s, m, p, a, k in ALL_GOLDEN])
def test_observed_run_matches_golden(scenario, mode, policy, aggregator, k,
                                     mlp_task, fl_data):
    rec = RunRecorder()
    digest = _run_case(scenario, mode, policy, mlp_task, fl_data,
                       aggregator=aggregator, k=k,
                       extra_cfg={"observe": rec})
    path = os.path.join(
        GOLDEN_DIR,
        f"{scenario}_{mode}_{policy if aggregator == 'fedavg' else aggregator}"
        ".json")
    with open(path) as fh:
        golden = json.load(fh)
    assert digest == golden, (
        f"{scenario}/{mode}: enabling observability changed the trajectory "
        "— the obs layer must be RNG-free and control-flow-free")
    rounds = [r for r in rec.records if r.get("type") == "round"]
    assert len(rounds) == len(golden)
    for r in rounds:
        assert all(key in r for key in ROUND_KEYS)
    assert not check_run(rounds, min_coverage=0.0)


def test_disabled_recorder_is_the_shared_null(mlp_task, fl_data):
    """observe unset -> the process-wide NULL_RECORDER, no profiler
    registration, and RoundResult still reports wall-time + executor (the
    cheap always-on fields)."""
    srv = FLServer(FLConfig(n_devices=8, k_select=2, rounds=2, l_ep=1,
                            seed=3, scenario="high-churn"),
                   mlp_task, fl_data)
    assert srv.obs is NULL_RECORDER
    assert active_profiler() is None
    hist = srv.run(build_policy("fedavg"))
    assert all(r.host_time_s > 0 for r in hist)
    assert all(r.executor for r in hist)
    assert NULL_RECORDER.records == []


# ---------------------------------------------------------------------------
# 2. span semantics + report reduction
# ---------------------------------------------------------------------------
def test_span_nesting_and_dual_clocks():
    rec = RunRecorder()
    t = {"now": 10.0}
    with rec.span("outer", clock=lambda: t["now"]):
        with rec.span("inner"):
            t["now"] = 17.0
    rec.flush_round(round=0, mode="sync", host_time_s=1.0)
    spans = rec.records[0]["spans"]
    # exit order: children before parents, paths carry the nesting
    assert [s["span"] for s in spans] == ["outer/inner", "outer"]
    assert all(s["wall_s"] >= 0 for s in spans)
    # virtual clock only on the span that was given one
    assert "v0_s" not in spans[0]
    assert spans[1]["v0_s"] == 10.0 and spans[1]["v1_s"] == 17.0


def test_virtual_time_is_independent_of_wall_time():
    rec = RunRecorder()
    t = {"now": 100.0}
    with rec.span("events", clock=lambda: t["now"]):
        t["now"] += 42.5          # virtual clock jumps; host wall is ~0
    rec.flush_round(round=0, mode="async", host_time_s=0.001)
    sp = rec.records[0]["spans"][0]
    assert sp["v1_s"] - sp["v0_s"] == pytest.approx(42.5)
    assert sp["wall_s"] < 1.0     # host wall measured separately
    table = phase_table([rec.records[0]])
    assert table[0]["virtual_s"] == pytest.approx(42.5)


def test_coverage_and_check_run():
    rounds = [{"type": "round", "round": 0, "mode": "sync",
               "host_time_s": 1.0, "ops": {}, "metrics": {},
               "spans": [{"span": "a", "wall_s": 0.5},
                         {"span": "a/nested", "wall_s": 0.4},
                         {"span": "b", "wall_s": 0.4}]}]
    # nested spans overlap their parents: only top-level counts
    assert coverage(rounds) == pytest.approx(0.9)
    assert check_run(rounds) == []
    assert check_run(rounds, min_coverage=0.95)  # too little accounted
    bad = [dict(rounds[0])]
    del bad[0]["metrics"]
    assert any("missing keys" in p for p in check_run(bad))
    assert check_run([]) == ["no round records"]


def test_report_tables_and_render():
    rec = RunRecorder()
    with rec.span("aggregate"):
        pass
    rec.record_op("select_topk.xla", 0.25)
    rec.record_op("select_topk.xla", 0.25)
    rec.flush_round(round=0, mode="sync", host_time_s=1.0)
    rounds = rec.records
    ops = op_table(rounds)
    assert ops == [{"op": "select_topk.xla", "n": 2, "wall_s": 0.5}]
    out = render({"scenario": "high-churn", "seed": 7,
                  "config_digest": "ab" * 32, "platform": {"backend": "cpu"}},
                 rounds, [])
    assert "scenario=high-churn" in out
    assert "select_topk.xla" in out
    assert "aggregate" in out


# ---------------------------------------------------------------------------
# 3. JSONL schema round-trip
# ---------------------------------------------------------------------------
def test_jsonl_round_trip(tmp_path, mlp_task, fl_data):
    out = tmp_path / "run"
    _run_case("high-churn", "async", "fedavg", mlp_task, fl_data,
              extra_cfg={"observe": str(out)})
    manifest, rounds, events = load_run(str(out))
    assert manifest["schema_version"] == 1
    assert manifest["scenario"] == "high-churn"
    assert manifest["seed"] == 7
    assert len(manifest["config_digest"]) == 64
    assert "jax" in manifest["versions"]
    assert rounds and all(all(k in r for k in ROUND_KEYS) for r in rounds)
    assert all(r["mode"] == "async" for r in rounds)
    # structured log events interleave with the round records
    assert any(e["event"] == "aggregation" for e in events)
    # virtual clock on the async engine spans, monotone across the run
    v1s = [sp["v1_s"] for r in rounds for sp in r["spans"] if "v1_s" in sp]
    assert v1s == sorted(v1s)
    assert not check_run(rounds, min_coverage=0.0)


def test_jsonl_file_matches_memory(tmp_path):
    rec = RunRecorder(out_dir=str(tmp_path / "r"))
    rec.event("hello", value=1)
    with rec.span("phase"):
        pass
    rec.metrics.gauge("fill", 3)
    rec.flush_round(round=0, mode="sync", host_time_s=0.5)
    rec.close()
    _, rounds, events = load_run(str(tmp_path / "r"))
    assert rounds + events == [r for r in rec.records
                               if r["type"] == "round"] + \
                              [r for r in rec.records if r["type"] == "event"]
    assert rounds[0]["metrics"]["gauges"] == {"fill": 3.0}


# ---------------------------------------------------------------------------
# 4. determinism modulo wall-time
# ---------------------------------------------------------------------------
def test_run_records_deterministic_modulo_wall(mlp_task, fl_data):
    recs = []
    for _ in range(2):
        rec = RunRecorder()
        _run_case("high-churn", "async", "fedavg", mlp_task, fl_data,
                  extra_cfg={"observe": rec})
        recs.append(rec.records)
    assert _scrub(recs[0]) == _scrub(recs[1])
    # and the scrub actually removed the volatile keys
    blob = json.dumps(_scrub(recs[0]))
    assert "wall_s" not in blob and "host_time_s" not in blob


# ---------------------------------------------------------------------------
# async stall diagnostics route through the recorder/logger
# ---------------------------------------------------------------------------
def test_async_stall_emits_structured_event(mlp_task, fl_data, monkeypatch):
    rec = RunRecorder()
    srv = FLServer(FLConfig(n_devices=8, k_select=2, rounds=2, l_ep=1,
                            seed=3, scenario="high-churn", mode="async",
                            async_concurrency=4, observe=rec),
                   mlp_task, fl_data)
    monkeypatch.setattr(AsyncRoundEngine, "_ready", lambda self: False)
    monkeypatch.setattr(AsyncRoundEngine, "_dispatch", lambda self: False)
    monkeypatch.setattr(AsyncRoundEngine, "_step", lambda self: False)
    with pytest.raises(AsyncStallError) as exc:
        srv.run(build_policy("fedavg"))
    assert exc.value.fields["aggregations_done"] == 0
    stalls = [r for r in rec.records if r.get("event") == "async-stall"]
    assert len(stalls) == 1
    assert stalls[0]["level"] == "error"
    assert stalls[0]["aggregations_target"] == 2
    assert stalls[0]["jobs_in_flight"] == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_snapshot_and_reset():
    m = MetricsRegistry()
    m.count("failures")
    m.count("failures", 2)
    m.gauge("fill", 5)
    m.gauge("fill", 7)                 # last write wins
    m.observe("staleness", [1.0, 3.0])
    m.observe("staleness", 5.0)        # scalars append too
    m.observe("empty", [])             # empty feeds record nothing
    snap = m.snapshot()
    assert snap["counters"] == {"failures": 3}
    assert snap["gauges"] == {"fill": 7.0}
    assert snap["histograms"] == {
        "staleness": {"n": 3, "mean": 3.0, "min": 1.0, "max": 5.0}}
    # reset=True cleared the window
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------
def test_logger_level_threshold_and_force():
    out = io.StringIO()
    log = StructuredLogger(level="warning", stream=out)
    log.info("quiet", x=1)
    assert out.getvalue() == ""
    log.warning("loud", x=2)
    assert out.getvalue() == "[repro.fl] loud x=2\n"
    log.log("forced", force=True, acc=0.51234)
    assert "forced acc=0.5123" in out.getvalue()   # floats render as .4g
    with pytest.raises(ValueError):
        StructuredLogger(level="verbose")


def test_logger_env_fallback_and_recorder_feed(monkeypatch):
    monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
    out = io.StringIO()
    rec = RunRecorder()
    log = StructuredLogger(stream=out, recorder=rec)
    log.debug("dbg", k=1)
    assert "dbg k=1" in out.getvalue()
    # the recorder gets the event regardless of console visibility
    assert rec.records == [{"type": "event", "event": "dbg",
                            "level": "debug", "k": 1}]
    # a disabled recorder gets nothing
    quiet = StructuredLogger(level="error", recorder=NULL_RECORDER)
    quiet.info("dropped")
    assert NULL_RECORDER.records == []


# ---------------------------------------------------------------------------
# manifest + recorder construction
# ---------------------------------------------------------------------------
def test_config_digest_ignores_observe(tmp_path):
    a = FLConfig(n_devices=10, seed=1, scenario="high-churn")
    b = FLConfig(n_devices=10, seed=1, scenario="high-churn",
                 observe=str(tmp_path))
    c = FLConfig(n_devices=11, seed=1, scenario="high-churn")
    assert config_digest(a) == config_digest(b)   # destination != identity
    assert config_digest(a) != config_digest(c)
    assert config_digest(a) == config_digest(a)   # stable
    man = run_manifest(a)
    assert man["config_digest"] == config_digest(a)
    assert man["config"]["n_devices"] == 10
    assert "observe" not in man["config"]


def test_manifest_names_the_device():
    import jax

    plat = run_manifest()["platform"]
    assert plat["backend"] == jax.default_backend()
    assert plat["device_kind"] == jax.devices()[0].device_kind
    assert plat["device_count"] == len(jax.devices())


def test_make_recorder_dispatch(tmp_path):
    assert make_recorder(None) is NULL_RECORDER
    assert make_recorder(False) is NULL_RECORDER
    mem = make_recorder(True, cfg=FLConfig(n_devices=4))
    assert mem.enabled and mem.out_dir is None
    assert mem.manifest["seed"] == FLConfig(n_devices=4).seed
    disk = make_recorder(str(tmp_path / "d"), cfg=FLConfig(n_devices=4))
    assert os.path.exists(tmp_path / "d" / "manifest.json")
    disk.close()
    pre = RunRecorder()
    assert make_recorder(pre) is pre              # pass-through
    with pytest.raises(ValueError):
        make_recorder(42)


# ---------------------------------------------------------------------------
# profiling hooks
# ---------------------------------------------------------------------------
def test_timed_call_passthrough_and_active():
    clear_profiler()
    assert timed_call("op", lambda a, b: a + b, 2, b=3) == 5  # passthrough
    rec = RunRecorder()
    set_profiler(rec)
    assert timed_call("op", lambda: 7) == 7
    assert timed_call("op", lambda: 9) == 9
    rec.flush_round(round=0, mode="sync", host_time_s=0.0)
    ops = rec.records[0]["ops"]
    assert ops["op"]["n"] == 2 and ops["op"]["wall_s"] >= 0
    # clearing with a stale recorder leaves a newer registration alone
    other = RunRecorder()
    set_profiler(other)
    clear_profiler(rec)
    assert active_profiler() is other
    clear_profiler(other)
    assert active_profiler() is None


def test_observed_server_registers_profiler(mlp_task, fl_data):
    rec = RunRecorder()
    srv = FLServer(FLConfig(n_devices=8, k_select=2, rounds=1, l_ep=1,
                            seed=3, scenario="high-churn", observe=rec),
                   mlp_task, fl_data)
    assert active_profiler() is rec
    hist = srv.run(build_policy("fedavg"))
    rounds = [r for r in rec.records if r.get("type") == "round"]
    # the executor op timing landed in the round record, attributed by label
    assert f"executor.{hist[0].executor}" in rounds[0]["ops"]
    assert rounds[0]["executor"] == hist[0].executor


# ---------------------------------------------------------------------------
# the host<->device boundary: train.* sub-spans, transfer and compile
# counters, fl: annotations
# ---------------------------------------------------------------------------
SYNC_TOP = {"plan", "probe", "select", "complete", "aggregate", "telemetry",
            "evaluate", "observe"}
ASYNC_TOP = {"ready_check", "aggregate", "dispatch", "events"}
TRAIN_SUBSPANS = {"train.gather", "train.pack", "train.upload", "train.pull",
                  "train.split"}


def _round_records(rec):
    return [r for r in rec.records if r.get("type") == "round"]


def _observed_vmapped_run(mlp_task, fl_data, mode, policy, rec, **kw):
    cfg = dict(n_devices=20, k_select=3, rounds=2, l_ep=2, lr=0.1, seed=7,
               scenario="high-churn", executor="vmapped", observe=rec)
    if mode == "async":
        cfg.update(mode="async", async_concurrency=6, staleness="polynomial")
    cfg.update(kw)
    srv = FLServer(FLConfig(**cfg), mlp_task, fl_data)
    pol_kw = {"k": 3, "seed": 7} if policy == "fedrank" else {}
    srv.run(build_policy(policy, **pol_kw))
    return srv


@pytest.mark.parametrize("mode,policy,stages", [
    ("sync", "fedrank", ("probe", "complete")),
    ("async", "fedavg", ("dispatch",)),
])
def test_vmapped_round_records_train_subspans(mlp_task, fl_data, mode,
                                              policy, stages):
    rec = RunRecorder()
    srv = _observed_vmapped_run(mlp_task, fl_data, mode, policy, rec)
    label = srv._executor_label
    tops = set()
    for r in _round_records(rec):
        paths = {sp["span"] for sp in r["spans"]}
        tops |= {p for p in paths if "/" not in p}
        for stage in stages:
            assert stage in paths
            # train.gather under the stage; the rest under the executor's
            # timed_call span, beside the fenced bucket step
            assert f"{stage}/train.gather" in paths
            ex = f"{stage}/executor.{label}"
            assert ex in paths
            for sub in TRAIN_SUBSPANS - {"train.gather"}:
                assert f"{ex}/{sub}" in paths
            assert any(p.startswith(f"{ex}/vmapped.bucket_step[")
                       for p in paths)
    # the sub-spans nest: the top-level phases are the engines' own
    assert tops == (SYNC_TOP if mode == "sync" else ASYNC_TOP)


def _cohort_h2d(requests, batch_size, params_host_bytes=0):
    """The bytes the vmapped executor uploads for ``requests`` (the rows
    themselves stay resident): per bucket each client's int32 row indices
    for every epoch, the learning rate, and the shared start's host
    leaves."""
    from repro.fl.client import _bucket_geometry

    buckets = {}
    for req in requests:
        cap, _, _ = _bucket_geometry(len(req.rows), batch_size)
        buckets.setdefault((cap, req.epochs), []).append(req)
    total = 0
    for (cap, epochs), reqs in buckets.items():
        _, bs, nb = _bucket_geometry(cap, batch_size)
        total += len(reqs) * epochs * nb * bs * 4  # int32 row indices
        total += 4 + params_host_bytes             # float32 lr, start
    return total, len(buckets)


@pytest.mark.parametrize("host_params", [False, True])
def test_h2d_train_counts_the_cohort(mlp_task, fl_data, host_params):
    import jax
    import numpy as np

    from repro.fl.engine import VmappedExecutor, build_requests

    params = mlp_task.init(jax.random.PRNGKey(0))
    if host_params:
        params = jax.tree.map(np.asarray, params)
    host_bytes = (sum(l.nbytes for l in jax.tree.leaves(params))
                  if host_params else 0)
    ids = [0, 3, 5, 11]

    reqs = build_requests(ids, fl_data, 2, seed=7, round_idx=0, stride=1000)
    rec = RunRecorder()
    set_profiler(rec)
    VmappedExecutor().run(mlp_task, params, reqs, lr=0.1, batch_size=10,
                          prox_mu=0.0)
    rec.flush_round(round=0, mode="sync", host_time_s=0.0)
    counters = rec.records[0]["metrics"]["counters"]
    want, n_buckets = _cohort_h2d(reqs, 10, host_bytes)
    assert counters["h2d_bytes.train"] == want
    # one blocking read per stacked leaf and one for the losses, per bucket
    n_leaves = len(jax.tree.leaves(params))
    assert counters["host_syncs.train"] == n_buckets * (n_leaves + 1)


def test_requests_read_the_clients_rows(fl_data):
    import numpy as np

    from repro.fl.engine import build_requests

    reqs = build_requests([2, 9, 17], fl_data, 1, seed=0, round_idx=0,
                          stride=0)
    for req in reqs:
        idx = fl_data.client_indices[req.client_id]
        assert req.rows is idx                 # the index array, not a copy
        np.testing.assert_array_equal(req.x, fl_data.train.x[idx])
        np.testing.assert_array_equal(req.y, fl_data.train.y[idx])


def test_resident_rows_upload_once(mlp_task):
    """Three observed rounds upload the training rows once, in the first
    stage; every stage after uploads row indices, lr and host params."""
    import jax
    import numpy as np

    from repro.data import FederatedData, dirichlet_partition, \
        make_classification_data
    from repro.fl.engine import VmappedExecutor

    train, test = make_classification_data(n_samples=1200, seed=4)
    data = FederatedData(train, test,
                         dirichlet_partition(train.y, 12, sigma=0.5, seed=4))
    calls = []

    class Spy(VmappedExecutor):
        def run(self, task, global_params, requests, **kw):
            host = sum(l.nbytes for l in jax.tree.leaves(global_params)
                       if not isinstance(l, jax.Array))
            calls.append((len(srv.history), list(requests), host))
            return super().run(task, global_params, requests, **kw)

    rec = RunRecorder()
    cfg = FLConfig(n_devices=12, k_select=3, rounds=3, l_ep=2, lr=0.1,
                   seed=4, observe=rec)
    srv = FLServer(cfg, mlp_task, data, executor=Spy())
    srv.run(build_policy("fedavg"))
    rounds = _round_records(rec)
    assert len(rounds) == 3
    counters = [r["metrics"]["counters"] for r in rounds]
    assert sum(c.get("resident_uploads", 0) for c in counters) == 1
    assert counters[0]["resident_uploads"] == 1
    n1 = len(train.y) + 1                      # rows + the padding row
    padded = n1 * train.x.shape[1] * 4 + n1 * 4
    assert sum(c.get("h2d_bytes.resident", 0) for c in counters) == padded
    for r, c in enumerate(counters):
        want = sum(_cohort_h2d(reqs, cfg.local_batch, host)[0]
                   for i, reqs, host in calls if i == r)
        assert want > 0
        assert c["h2d_bytes.train"] == want


def test_host_syncs_evaluate_two_per_batch(mlp_task, fl_data):
    rec = RunRecorder()
    _observed_vmapped_run(mlp_task, fl_data, "sync", "fedavg", rec, rounds=1)
    (r,) = _round_records(rec)
    counters = r["metrics"]["counters"]
    n_batches = -(-len(fl_data.test.y) // 512)
    assert counters["host_syncs.evaluate"] == 2 * n_batches
    # at least the evaluation rows crossed (plus any host-held params)
    rows = fl_data.test.x.nbytes + fl_data.test.y.nbytes
    assert counters["h2d_bytes.evaluate"] >= rows


def test_compile_counter_names_the_span_path():
    import jax
    import numpy as np

    rec = RunRecorder()
    set_profiler(rec)
    f = jax.jit(lambda x: x * 3.0 - 1.0)
    x = np.ones(37, np.float32)
    with rec.span("outer"):
        with rec.span("inner"):
            f(x)
            f(x)                         # same shape: cached, no compile
    with rec.span("outer"):
        f(x)
    rec.flush_round(round=0, mode="sync", host_time_s=0.0)
    counters = rec.records[0]["metrics"]["counters"]
    assert counters == {"compiles.outer/inner": 1}


def test_enabled_span_enters_a_trace_annotation(monkeypatch):
    import repro.obs.recorder as recorder

    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(recorder, "TraceAnnotation", Note)
    rec = RunRecorder()
    with rec.span("aggregate"):
        with rec.span("evaluate"):
            pass
    assert seen == [("enter", "fl:aggregate"), ("enter", "fl:evaluate"),
                    ("exit", "fl:evaluate"), ("exit", "fl:aggregate")]
    seen.clear()
    with NULL_RECORDER.span("aggregate"):
        pass
    assert seen == []


def test_unobserved_run_counts_nothing(mlp_task, fl_data, monkeypatch):
    from repro.obs import profiling

    clear_profiler()
    assert profiling.span("train.pack") is NULL_RECORDER.span("x")

    def boom(*_):
        raise AssertionError("byte counting ran with observability off")

    monkeypatch.setattr(profiling, "_tree_nbytes", boom)
    monkeypatch.setattr(profiling, "_leaves", boom)
    _observed_vmapped_run(mlp_task, fl_data, "sync", "fedrank", None)
    _observed_vmapped_run(mlp_task, fl_data, "async", "fedavg", None)


def test_unobserved_server_clears_a_stale_profiler(mlp_task, fl_data):
    stale = RunRecorder()
    _observed_vmapped_run(mlp_task, fl_data, "sync", "fedavg", stale,
                          rounds=1)
    assert active_profiler() is stale
    n_records = len(stale.records)
    srv = FLServer(FLConfig(n_devices=20, k_select=3, rounds=1, l_ep=2,
                            seed=7, scenario="high-churn",
                            executor="vmapped"), mlp_task, fl_data)
    assert active_profiler() is None
    srv.run(build_policy("fedavg"))
    stale.flush_round(round=99, mode="sync", host_time_s=0.0)
    # nothing of the second server's run reached the earlier recorder
    (last,) = stale.records[n_records:]
    assert last["spans"] == [] and last["ops"] == {}
    assert last["metrics"]["counters"] == {}
