#!/usr/bin/env python3
"""FL aggregation benchmark: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Set-up: the compile cache, the fleet's data and the initial weights made
   from ``--seed`` (:mod:`bench.fleet`), the program's server, policy and
   (async) engine (:mod:`bench.drive`), and aggregations on the cell's own
   traffic until nothing new compiles.
2. Window: closed-loop aggregations, back to back, for ``--seconds``; each
   timed by the host clock and fenced on the global params.
3. ``correct``: the first aggregation and a seeded sample of the window's,
   recomputed by the plain reference (:mod:`bench.check`).
4. One JSON line: with ``--trace 0`` the cell's end-to-end metrics, with
   ``--trace 1`` its per-layer metrics read from the program's spans, its
   counters and a profiler trace of the window (:mod:`bench.trace`).

Off the TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
TRACE_DIR = ROOT / ".bench_out" / "trace"


def device_check(chips: int) -> dict:
    """The chip's description; exits when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"bench: JAX found platform {d0.platform!r}, not 'tpu'")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def _tracing_recorder():
    """A ``repro.obs`` recorder whose spans also land in the profiler's
    trace as ``fl:<phase>`` annotations."""
    import jax
    from repro.obs.recorder import RunRecorder

    class Annotated:
        def __init__(self, span, name):
            self.span = span
            self.note = jax.profiler.TraceAnnotation("fl:" + name)

        def __enter__(self):
            self.note.__enter__()
            return self.span.__enter__()

        def __exit__(self, *exc):
            out = self.span.__exit__(*exc)
            self.note.__exit__(*exc)
            return out

    class TracingRecorder(RunRecorder):
        def span(self, name, clock=None):
            return Annotated(super().span(name, clock), name)

    return TracingRecorder()


class Window:
    """What the per-layer metric readers read (``bench/metrics``)."""

    def __init__(self, cell, seconds, n_aggs, spans, compiles, trace,
                 chips, peaks, rec):
        self.config = cell.config
        self.traffic = cell.traffic
        self.seconds = seconds
        self.n_aggs = n_aggs
        self.spans = spans
        self.compiles = compiles
        self.trace = trace
        self.chips = chips
        self.peaks = peaks
        self.select_calls = rec.select_calls
        self._rec = rec

    def span_ms_per_agg(self, *paths):
        hit = [self.spans[p] for p in paths if p in self.spans]
        if not hit or not self.n_aggs:
            return None
        return 1e3 * sum(hit) / self.n_aggs

    def model_flops(self) -> float:
        """FLOPs the window's work needs: the task's training and
        evaluation, and the policy's scoring."""
        from bench.fleet import module

        model = self.config["model"]
        task = module("tasks", model["task"])
        return (task.train_flops(model, self._rec.sample_epochs)
                + task.eval_flops(model, self.n_aggs
                                  * self.config["data"]["test_rows"])
                + module("policies", self.traffic["policy"]).flops(
                    self.traffic, self._rec.ranked_rows))


def _span_totals(records) -> dict:
    out = {}
    for rec in records:
        for sp in rec.get("spans", ()):
            out[sp["span"]] = out.get(sp["span"], 0.0) + float(sp["wall_s"])
    return out


def _per_layer(bench_spec, cell, window: Window) -> dict:
    out = {}
    for m in bench_spec["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace into this directory")
    args = ap.parse_args(argv)

    import jax

    from bench import check, drive, flops
    from bench.trace import STEP, reduce_trace
    from repro.launch.compile_cache import enable_compile_cache

    with open(ROOT / "BENCHMARK.json") as fh:
        bench_spec = json.load(fh)
    cell = drive.Cell(args.workload)
    enable_compile_cache()
    # cache every program, however quick to compile: set-up then pays no
    # compile after a checkout's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_check(cell.chips)
    peaks = flops.peaks(device["kind"])

    clock = drive.CompileClock()
    rec = drive.Recorder(args.seed, cell.spec["compare"])
    observer = _tracing_recorder() if args.trace else None
    t_build = time.perf_counter()
    system = drive.System(cell, args.seed, rec, observe=observer)
    t_warm = time.perf_counter()
    n_warm = drive.warm_up(system, clock)
    print(f"set-up: {t_build - T_PROCESS:.2f} s to the chip, "
          f"{t_warm - t_build:.2f} s data, weights and server; warm-up: "
          f"{n_warm} aggregations, {clock.count()} compiles in "
          f"{time.perf_counter() - t_warm:.2f} s", file=sys.stderr, flush=True)

    # ---- window ------------------------------------------------------------
    rec.sample_epochs = rec.ranked_rows = 0
    rec.select_calls.clear()
    first_record = len(observer.records) if observer else 0
    step = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

        def step(i):
            return jax.profiler.StepTraceAnnotation(STEP, step_num=i)
    setup_s = time.perf_counter() - T_PROCESS
    try:
        t0, t1, times, merged, failed = drive.run_window(
            system, args.seconds, n_warm, on_step=step)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    window_s = t1 - t0
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])

    # ---- correct -------------------------------------------------------------
    view = check.TraceView(system.srv.pool, cell.config["fleet"]["trace_states"])
    t_check = time.perf_counter()
    nums = check.numbers(rec.kept, rec.avail, system.init_params,
                         cell.config, cell.traffic, view)
    limits = cell.spec["limits"]
    finite = check.is_finite(system.srv.global_params)
    compared = len(rec.kept) - (0 in rec.kept)
    failed += 0 if finite else 1
    ok = (failed == 0 and compared >= 1
          and all(nums[k] <= limits[k] for k in limits))
    # the process's host peak so far (KiB on Linux): what a cell's data,
    # program and check cost the host
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    print(f"check: {compared} window aggregations compared in "
          f"{time.perf_counter() - t_check:.1f} s; host peak RSS "
          f"{rss:.2f} GiB; final params "
          f"{'finite' if finite else 'NOT finite'}", file=sys.stderr)

    # ---- metrics ---------------------------------------------------------------
    n = len(times)
    result = {"correct": bool(ok), "attempted": n + failed - (not finite),
              "failed": failed}
    if args.trace:
        path = next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"), None)
        reduced = reduce_trace(str(path)) if path else None
        if args.keep_trace and path:
            shutil.copytree(path.parent, Path(args.keep_trace),
                            dirs_exist_ok=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        spans = _span_totals(observer.records[first_record:])
        win = Window(cell, window_s, n, spans,
                     clock.count(t0, t1), reduced, cell.chips, peaks, rec)
        result["metrics"] = _per_layer(bench_spec, cell, win)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {
                "device_ops": [list(kv) for kv in top(reduced["modules"])],
                "idle_gaps": [list(kv) for kv in
                              top(reduced["idle_by_phase"])]}
    else:
        metrics = {"updates_per_s": merged / window_s,
                   "agg_p95_ms": 1e3 * statistics.quantiles(
                       times, n=20)[-1] if n >= 2 else float("nan"),
                   "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in bench_spec["end_to_end"]
            if cell.name in m.get("workloads", [cell.name])}
    result["device"] = device
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    print(f"window: {n} aggregations, {merged} updates in {window_s:.3f} s; "
          f"set-up {setup_s:.2f} s; median aggregation "
          f"{1e3 * statistics.median(times) if times else float('nan'):.1f} ms",
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
