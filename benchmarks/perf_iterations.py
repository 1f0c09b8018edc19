"""§Perf hillclimb harness: named iterations over the three chosen
(arch x shape) pairs, each re-lowered + re-analyzed on the production mesh.

MUST run in its own process (sets the 512-device flag):
    PYTHONPATH=src python -m benchmarks.perf_iterations --out results/perf.json

FL round-engine modes (real CPU timing, so NO 512-device flag):
    PYTHONPATH=src python -m benchmarks.perf_iterations --fl-executors
    PYTHONPATH=src python -m benchmarks.perf_iterations --fl-modes [--quick]

``--fl-executors`` compares the sequential reference ClientExecutor against
the vmapped pod-scale executor on wall-clock time per FL round across
cohort sizes; ``--fl-modes`` compares the synchronous barrier engine
against the asynchronous buffered engine on simulated
wall-clock-to-accuracy per scenario (see docs/benchmarks.md).
"""
import os
import sys

# the dry-run experiments need the 512-device host platform; the FL executor,
# FL mode and fleet timing modes need the real single CPU device — decide
# before jax loads
if ("--fl-executors" not in sys.argv and "--fleet" not in sys.argv
        and "--fl-modes" not in sys.argv):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
from typing import Any, Dict


def _summ(rec: Dict[str, Any]) -> Dict[str, Any]:
    from repro.launch.roofline import row_from_record

    if rec["status"] != "ok":
        return {"status": rec["status"], "error": rec.get("error", "")[:200]}
    row = row_from_record(rec)
    return {
        "status": "ok",
        "compute_s": round(row.compute_s, 4),
        "memory_s": round(row.memory_s, 4),
        "collective_s": round(row.collective_s, 4),
        "dominant": row.dominant,
        "useful_ratio": round(row.useful_ratio, 4),
        "temp_GB": round(rec.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9, 1),
        "flops_per_device": rec["hlo"]["flops_per_device"],
        "bytes_per_device": rec["hlo"]["bytes_per_device"],
        "convert_bytes_per_device": rec["hlo"].get("convert_bytes_per_device", 0),
        "collective_wire_bytes": rec["hlo"]["collective_wire_bytes"],
        "compile_s": rec["compile_s"],
    }


# Each experiment: (pair_name, arch, shape, iteration_name, run_one kwargs)
EXPERIMENTS = [
    # ---- pair A: hymba-1.5b train_4k — worst roofline fraction -----------
    ("hymba_train", "hymba-1.5b", "train_4k", "baseline", {}),
    ("hymba_train", "hymba-1.5b", "train_4k", "it1_unroll8_mamba_scan",
     {"cfg_overrides": {"__ssm_unroll": 8}}),
    ("hymba_train", "hymba-1.5b", "train_4k", "it2_unroll16",
     {"cfg_overrides": {"__ssm_unroll": 16}}),
    ("hymba_train", "hymba-1.5b", "train_4k", "it3_unroll8_seqshard",
     {"cfg_overrides": {"__ssm_unroll": 8}, "seq_shard": True}),
    # ---- pair B: internvl2-76b train_4k — most collective-bound ----------
    ("internvl_train", "internvl2-76b", "train_4k", "baseline", {}),
    ("internvl_train", "internvl2-76b", "train_4k", "it1_seq_shard",
     {"seq_shard": True}),
    ("internvl_train", "internvl2-76b", "train_4k", "it2_seq_shard_naive_attn",
     {"seq_shard": True, "impl": "naive"}),
    ("internvl_train", "internvl2-76b", "train_4k", "it3_fsdp_on_output",
     {"seq_shard": True, "fsdp_on_output": True}),
    ("internvl_train", "internvl2-76b", "train_4k", "it4_weights_tp_only",
     {"seq_shard": True, "weights_tp_only": True}),
    # ---- pair C: olmoe-1b-7b train_4k — the MoE/EP technique pair --------
    ("olmoe_train", "olmoe-1b-7b", "train_4k", "baseline_sort_dispatch", {}),
    ("olmoe_train", "olmoe-1b-7b", "train_4k", "ref_dense_gshard_dispatch",
     {"moe_dispatch": "dense"}),
    ("olmoe_train", "olmoe-1b-7b", "train_4k", "it1_seq_shard",
     {"seq_shard": True}),
    ("olmoe_train", "olmoe-1b-7b", "train_4k", "it2_seqshard_cap1.0",
     {"seq_shard": True, "cfg_overrides": {"__moe_cap": 1.0}}),
]


def _apply_special_overrides(kwargs: Dict[str, Any], arch: str):
    """Translate pseudo-overrides into dataclass replaces."""
    import dataclasses

    from repro.configs import get_model_config

    co = dict(kwargs.pop("cfg_overrides", {}) or {})
    unroll = co.pop("__ssm_unroll", None)
    cap = co.pop("__moe_cap", None)
    cfg = get_model_config(arch)
    changed = dict(co)
    if unroll is not None:
        changed["ssm"] = dataclasses.replace(cfg.ssm, scan_unroll=unroll)
    if cap is not None:
        changed["moe"] = dataclasses.replace(cfg.moe, capacity_factor=cap)
    if changed:
        kwargs["cfg_overrides"] = changed
    return kwargs


# ---------------------------------------------------------------------------
# FL round-engine comparison: sequential vs vmapped ClientExecutor
# ---------------------------------------------------------------------------


def run_fl_executor_bench(ks=(4, 8, 16, 32), rounds: int = 3,
                          l_ep: int = 3, verbose: bool = True):
    """Steady-state wall-clock per FL round for each executor at cohort size
    K (all K clients selected each round, equal-size shards so the vmapped
    path runs one bucket = one jitted step per stage)."""
    from repro.data import FederatedData, iid_partition, make_classification_data
    from repro.fl import FLConfig, FLServer, MLPTask, build_policy

    rows = []
    for k in ks:
        n_devices = int(k)
        train, test = make_classification_data(n_samples=256 * n_devices, seed=0)
        parts = iid_partition(len(train.y), n_devices, seed=0, size_skew=0.0)
        data = FederatedData(train, test, parts)
        task = MLPTask(dim=32, hidden=64, n_classes=10)
        per_round, per_stage = {}, {}
        for executor in ("sequential", "vmapped"):
            cfg = FLConfig(n_devices=n_devices, k_select=k, rounds=rounds,
                           l_ep=l_ep, lr=0.1, seed=0, executor=executor)
            srv = FLServer(cfg, task, data)
            policy = build_policy("fedavg")
            srv.run_round(policy)              # warmup: jit compile
            t0 = time.perf_counter()
            for _ in range(rounds):
                srv.run_round(policy)
            per_round[executor] = (time.perf_counter() - t0) / rounds
            # stage-level: executor.run alone, isolating client execution
            # from eval/selection/cost accounting shared by both executors
            from repro.fl.engine import build_requests

            reqs = build_requests(range(k), data, l_ep, seed=0, round_idx=0,
                                  stride=0)
            srv._execute(reqs)                 # warmup for this shape
            t0 = time.perf_counter()
            for _ in range(rounds):
                srv._execute(reqs)
            per_stage[executor] = (time.perf_counter() - t0) / rounds
        row = {"bench": "fl_round_engine", "k": k, "l_ep": l_ep,
               "sequential_round_s": round(per_round["sequential"], 4),
               "vmapped_round_s": round(per_round["vmapped"], 4),
               "speedup": round(per_round["sequential"] / per_round["vmapped"], 2),
               "sequential_exec_s": round(per_stage["sequential"], 4),
               "vmapped_exec_s": round(per_stage["vmapped"], 4),
               "exec_speedup": round(per_stage["sequential"] / per_stage["vmapped"], 2)}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    return rows


# ---------------------------------------------------------------------------
# FL round-regime comparison: sync barrier vs async buffered aggregation
# ---------------------------------------------------------------------------


def run_fl_modes_bench(scenarios=("uniform", "high-churn"), quick: bool = False,
                       verbose: bool = True):
    """Simulated wall-clock-to-accuracy of the synchronous barrier engine vs
    the asynchronous buffered engine (buffer=K, concurrency=3K, polynomial
    staleness) per scenario.  The sync run fixes the accuracy target (its
    final accuracy); the async run reports when it crosses that target on
    its virtual clock.  ``--quick`` shrinks everything to a CI smoke."""
    from repro.data import FederatedData, dirichlet_partition, \
        make_classification_data
    from repro.fl import FLConfig, FLServer, MLPTask, build_policy

    n_devices, k, l_ep = (16, 3, 2) if quick else (20, 4, 2)
    sync_rounds = 2 if quick else 20
    async_aggs = 4 if quick else 60
    train, test = make_classification_data(
        n_samples=2000 if quick else 4000, seed=0)
    parts = dirichlet_partition(train.y, n_devices, 0.1, seed=0)
    data = FederatedData(train, test, parts)
    task = MLPTask(dim=32, hidden=32, n_classes=10)

    rows = []
    for scenario in scenarios:
        kw = dict(n_devices=n_devices, k_select=k, l_ep=l_ep, lr=0.1,
                  seed=0, scenario=scenario)
        srv_sync = FLServer(FLConfig(rounds=sync_rounds, **kw), task, data)
        hist_sync = srv_sync.run(build_policy("fedavg"))
        target = hist_sync[-1].acc
        t_sync = hist_sync[-1].cum_time

        srv_async = FLServer(FLConfig(rounds=async_aggs, mode="async",
                                      async_concurrency=3 * k,
                                      staleness="polynomial", **kw),
                             task, data)
        hist_async = srv_async.run(build_policy("fedavg"))
        hit = next((r for r in hist_async if r.acc >= target), None)
        row = {"bench": "fl_round_modes", "scenario": scenario,
               "k": k, "l_ep": l_ep, "sync_rounds": sync_rounds,
               "target_acc": round(target, 4),
               "sync_time_s": round(t_sync, 1),
               "async_toa_s": round(hit.cum_time, 1) if hit else "n/a",
               "async_aggs_to_target": hit.round if hit else "n/a",
               "async_final_acc": round(hist_async[-1].acc, 4),
               "async_speedup": (round(t_sync / hit.cum_time, 2)
                                 if hit else "n/a"),
               "async_mean_staleness": round(
                   sum(r.mean_staleness for r in hist_async)
                   / len(hist_async), 2)}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    return rows


# ---------------------------------------------------------------------------
# Fleet-scale DevicePool: vectorized struct-of-arrays vs seed per-object impl
# ---------------------------------------------------------------------------


class _LegacyDevicePool:
    """The seed repo's per-object DevicePool, kept verbatim as the reference
    the vectorized implementation is benchmarked against."""

    _TIERS = [
        (1.2e9, 12.5e6, 4.0e-9, 1.5e-7),
        (3.5e8, 5.0e6, 1.0e-8, 3.0e-7),
        (6.0e7, 1.5e6, 2.5e-8, 6.0e-7),
    ]
    _LOAD_LEVELS = None  # set lazily (numpy import order)
    _LOAD_TRANS = None

    def __init__(self, n_devices, seed=0, tier_probs=None):
        import numpy as np
        from dataclasses import dataclass

        if _LegacyDevicePool._LOAD_LEVELS is None:
            _LegacyDevicePool._LOAD_LEVELS = np.array([1.0, 0.55, 0.25])
            _LegacyDevicePool._LOAD_TRANS = np.array([
                [0.80, 0.15, 0.05], [0.30, 0.55, 0.15], [0.15, 0.35, 0.50]])

        @dataclass
        class _Profile:
            speed: float
            bandwidth: float
            j_per_flop: float
            j_per_byte: float
            tier: int

        self.n = n_devices
        self.rng = np.random.default_rng(seed)
        tier_probs = tier_probs or [0.25, 0.5, 0.25]
        self.devices = []
        for _ in range(n_devices):
            t = int(self.rng.choice(len(self._TIERS), p=tier_probs))
            sp, bw, jf, jb = self._TIERS[t]
            jitter = lambda: float(self.rng.lognormal(0.0, 0.25))
            self.devices.append(_Profile(
                speed=sp * jitter(), bandwidth=bw * jitter(),
                j_per_flop=jf * jitter(), j_per_byte=jb * jitter(), tier=t))
        self._load_state = self.rng.integers(0, 3, size=n_devices)

    def advance_round(self):
        import numpy as np

        u = self.rng.random(self.n)
        cdf = np.cumsum(self._LOAD_TRANS[self._load_state], axis=1)
        self._load_state = (u[:, None] > cdf).sum(axis=1)

    def system_state(self, flops_per_epoch, model_bytes):
        import numpy as np

        speed = np.array([d.speed for d in self.devices])
        bw = np.array([d.bandwidth for d in self.devices])
        jf = np.array([d.j_per_flop for d in self.devices])
        jb = np.array([d.j_per_byte for d in self.devices])
        load = self._LOAD_LEVELS[self._load_state]
        return (flops_per_epoch / (speed * load),
                2.0 * model_bytes / bw + 2.0,
                flops_per_epoch * jf, 2.0 * model_bytes * jb)

    def static_estimates(self, flops_per_epoch, model_bytes, l_ep):
        import numpy as np

        speed = np.array([d.speed for d in self.devices])
        bw = np.array([d.bandwidth for d in self.devices])
        jf = np.array([d.j_per_flop for d in self.devices])
        jb = np.array([d.j_per_byte for d in self.devices])
        t = 2 * model_bytes / bw + 2.0 + l_ep * flops_per_epoch / speed
        e = 2 * model_bytes * jb + l_ep * flops_per_epoch * jf
        return t, e


def run_fleet_bench(sizes=(10_000, 100_000), steps: int = 5, repeats: int = 3,
                    verbose: bool = True):
    """Build + per-round simulator work for the vectorized DevicePool vs the
    seed per-object reference, plus the trace-replay fleet
    (``trace-synthetic-week`` resampled to the same size — the acceptance
    bar is that a trace fleet builds/steps in the same order of magnitude
    as the vectorized synthetic scenarios).  One "step" is what the seed
    server did every round: advance the dynamics, rebuild the system state,
    and recompute the static estimates (the current server caches the
    round-invariant estimates once, so the vectorized side only pays
    advance+state).  Best of ``repeats`` (min is the stable estimator under
    allocator noise)."""
    import numpy as np

    from repro.fl.scenarios import get_scenario
    from repro.fl.simulation import DevicePool, static_estimates

    # one-time source-trace synthesis+compilation is process-wide (memoized
    # per TraceSpec); pay it before timing so rows measure fleet work
    trace_spec = get_scenario("trace-synthetic-week")
    trace_spec.trace.trace()

    def _trace_pool(n, seed=0):
        return trace_spec.build(n, seed=seed)

    rows = []
    for n in sizes:
        fpe = np.full(n, 1e9)
        timings = {}
        for name, cls in (("legacy", _LegacyDevicePool),
                          ("vectorized", DevicePool),
                          ("trace", _trace_pool)):
            build_s, step_s = float("inf"), float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                pool = cls(n, seed=0)
                if name != "legacy":
                    static_estimates(pool, fpe, 1e6, 3)   # cached by the server
                build_s = min(build_s, time.perf_counter() - t0)
                t0 = time.perf_counter()
                for _ in range(steps):
                    pool.advance_round()
                    pool.system_state(fpe, 1e6)
                    if name == "legacy":                  # seed: every round
                        pool.static_estimates(fpe, 1e6, 3)
                step_s = min(step_s, (time.perf_counter() - t0) / steps)
            timings[name] = (build_s, step_s)
        (lb, ls), (vb, vs) = timings["legacy"], timings["vectorized"]
        tb, ts = timings["trace"]
        row = {"bench": "fleet_scale", "n_devices": n, "steps": steps,
               "legacy_build_s": round(lb, 4), "vectorized_build_s": round(vb, 5),
               "legacy_step_s": round(ls, 4), "vectorized_step_s": round(vs, 5),
               "trace_build_s": round(tb, 5), "trace_step_s": round(ts, 5),
               "build_speedup": round(lb / vb, 1),
               "step_speedup": round(ls / vs, 1),
               "build_plus_step_speedup": round((lb + ls) / (vb + vs), 1),
               "trace_build_vs_vectorized": round(tb / vb, 2),
               "trace_step_vs_vectorized": round(ts / vs, 2)}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    return rows


# ---------------------------------------------------------------------------
# Fleet-scale async event loop: batched windows vs sequential vs PR-8 baseline
# ---------------------------------------------------------------------------


def run_async_step_bench(sizes=None, quick: bool = False,
                         verbose: bool = True):
    """Wall-clock per simulated aggregation of the async engine's event loop
    at fleet scale (``trace-synthetic-week`` resampled to N devices, one
    sample per client and a pinned 2k-row test set so client training and
    evaluation are negligible and the rows time the LOOP: event stepping,
    dispatch bookkeeping, pool replay).

    Three implementations:

    * ``baseline`` — the PR-8 event loop's cost model: one event instant
      per step with THREE Python sweeps over the in-flight ``AsyncJob``
      dataclasses (``_next_event_dt`` min-scan / ``_advance`` elapsed
      updates / ``_process_events`` due filter — O(concurrency) per
      event), per-round ``next_transition`` scanning
      (``REPRO_TRACE_TRANSITION=scan``) and round-by-round pool replay
      (``stateless_replay`` fast path disabled).  Skipped above 100k
      devices — the scan alone is O(rounds_per_period x N) per transition;
    * ``sequential`` — the absolute-time oracle over the fused trace
      timeline kernel (one event instant per step, struct-of-arrays job
      state, no per-job Python objects);
    * ``batched`` — the compiled event loop: whole event windows per step.

    ``--quick`` (the CI smoke) runs 100k devices only and ASSERTS the
    batched loop beats the sequential oracle."""
    import numpy as np

    from repro.data import FederatedData, iid_partition, \
        make_classification_data
    from repro.fl import FLConfig, FLServer, MLPTask, build_policy
    from repro.fl.simulation import DevicePool

    sizes = sizes or ((100_000,) if quick else (100_000, 1_000_000))
    aggs = 3 if quick else 5
    k, conc = 512, 8192
    task = MLPTask(dim=32, hidden=32, n_classes=10)

    def _data(n):
        train, test = make_classification_data(n_samples=n, seed=0)
        test = type(test)(test.x[:2000], test.y[:2000], test.n_classes)
        parts = iid_partition(len(train.y), n, seed=0, size_skew=0.0)
        return FederatedData(train, test, parts)

    def _run(n, data, impl):
        cfg = FLConfig(
            n_devices=n, k_select=k, rounds=aggs, l_ep=1, lr=0.1, seed=0,
            scenario="trace-synthetic-week", mode="async",
            async_concurrency=conc, staleness="polynomial",
            executor="vmapped",
            async_events="batched" if impl == "batched" else "sequential")
        srv = FLServer(cfg, task, data)
        t0 = time.perf_counter()
        srv.run(build_policy("fedavg"))
        return (time.perf_counter() - t0) / aggs

    def _run_baseline(n, data):
        # Emulate the PR-8 loop on top of the (behaviour-identical)
        # sequential oracle: same history, pre-compiled-loop costs.
        from repro.fl.async_engine import _EPS, AsyncJob, AsyncRoundEngine

        orig_advance = DevicePool.advance_to
        orig_step = AsyncRoundEngine._step_sequential

        def loop_advance(self, round_idx):
            while self.round_idx < round_idx:
                self.advance_round()

        def legacy_step(self):
            # The PR-8 engine kept one AsyncJob dataclass per in-flight job
            # and swept them all, three times, at every event instant.
            # Replay those sweeps (pure cost model — the oracle step below
            # still drives all actual state).
            jt = self.jobs
            mirror = self.__dict__.setdefault("_legacy_jobs", {})
            live = np.flatnonzero(jt.active).tolist()
            for s in live:
                if s not in mirror:
                    job = AsyncJob(
                        cid=int(jt.cid[s]), version=int(jt.version[s]),
                        seq=int(jt.seq[s]), cycle=int(jt.cycle[s]),
                        duration_s=float(jt.duration[s]), energy_j=0.0,
                        params=None, loss=0.0,
                        fail_at_s=float(jt.fail_at[s]))
                    job.elapsed_s = 0.0
                    mirror[s] = job
            for s in set(mirror) - set(live):
                del mirror[s]
            mask, jobs = self._mask, list(mirror.values())
            dts = [j.end_s - j.elapsed_s for j in jobs
                   if mask[j.cid]]                       # _next_event_dt
            if dts:
                dt = max(min(dts), 0.0)
                for j in jobs:                           # _advance
                    if mask[j.cid]:
                        j.elapsed_s += dt
                _ = [j for j in jobs
                     if j.elapsed_s >= j.end_s - _EPS]   # _process_events
            return orig_step(self)

        os.environ["REPRO_TRACE_TRANSITION"] = "scan"
        DevicePool.advance_to = loop_advance
        AsyncRoundEngine._step_sequential = legacy_step
        try:
            return _run(n, data, "sequential")
        finally:
            del os.environ["REPRO_TRACE_TRANSITION"]
            DevicePool.advance_to = orig_advance
            AsyncRoundEngine._step_sequential = orig_step

    _run(1000, _data(1000), "batched")       # warmup: jit compile

    rows = []
    for n in sizes:
        data = _data(n)
        seq_s = min(_run(n, data, "sequential") for _ in range(2))
        bat_s = min(_run(n, data, "batched") for _ in range(2))
        base_s = _run_baseline(n, data) if n <= 100_000 else None
        row = {"bench": "async_step", "n_devices": n, "aggregations": aggs,
               "k": k, "concurrency": conc,
               "baseline_agg_s": round(base_s, 4) if base_s else "skipped",
               "sequential_agg_s": round(seq_s, 4),
               "batched_agg_s": round(bat_s, 4),
               "batched_vs_sequential": round(seq_s / bat_s, 2),
               "batched_vs_baseline": (round(base_s / bat_s, 1)
                                       if base_s else "n/a")}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
        if quick and n >= 100_000:
            assert bat_s < seq_s, (
                f"batched event loop ({bat_s:.3f}s/agg) did not beat the "
                f"sequential oracle ({seq_s:.3f}s/agg) at {n} devices")
    return rows


# ---------------------------------------------------------------------------
# Fleet-scale cohort selection: host score+argsort vs the select_topk op
# ---------------------------------------------------------------------------


def run_selection_bench(sizes=(10_000, 100_000), k: int = 64,
                        repeats: int = 5, verbose: bool = True):
    """Top-K cohort cut over an N-device fleet: the seed host path (score
    everything, pull the full ``(N,)`` vector to host, full ``np.argsort``)
    vs the shared :func:`repro.kernels.select_topk.ops.select_topk` op
    (fused score+top-K in one jitted call, only K winners leave the
    device).  Same Q-net, same mask, identical winners (the op's parity is
    pinned in tests/test_select_topk.py); this times the round-trip + sort.
    Best of ``repeats``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.qnet import apply_qnet, init_qnet
    from repro.kernels.select_topk.ops import select_topk

    params = init_qnet(jax.random.PRNGKey(0))
    f = int(params["w1"].shape[0])
    rows = []
    for n in sizes:
        rng = np.random.default_rng(0)
        feats = rng.normal(size=(n, f)).astype(np.float32)
        mask = (rng.random(n) > 0.1).astype(np.float32)

        def host_path():
            qs = np.asarray(apply_qnet(params, jnp.asarray(feats)))
            qs = np.where(mask > 0, qs, -np.inf)
            return np.argsort(-qs, kind="stable")[:k]

        def op_path():
            return select_topk(params, feats, mask, k)[0]

        host_s, op_s = float("inf"), float("inf")
        a, b = host_path(), op_path()            # warmup: jit compile
        assert np.array_equal(np.asarray(a), np.asarray(b)), "selection parity"
        for _ in range(repeats):
            t0 = time.perf_counter()
            host_path()
            host_s = min(host_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            op_path()
            op_s = min(op_s, time.perf_counter() - t0)
        row = {"bench": "selection", "n_devices": n, "k": k,
               "host_argsort_s": round(host_s, 5),
               "select_topk_s": round(op_s, 5),
               "speedup": round(host_s / op_s, 2)}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    return rows


# ---------------------------------------------------------------------------
# Hierarchical round execution: region-vectorized (stacked) vs sequential
# ---------------------------------------------------------------------------


def run_region_exec_bench(ks=(6, 12), rounds: int = 3, l_ep: int = 2,
                          verbose: bool = True):
    """Steady-state wall-clock per hierarchical round with the per-region
    cohorts executed as ONE stacked executor call (``region_exec="stacked"``,
    the default — mesh-shardable) vs one executor call per region
    (``region_exec="sequential"``).  Both paths are numerically identical
    (see tests/test_topology.py); this times the fan-out.  Equal-size
    shards (as in :func:`run_fl_executor_bench`) + always-available
    ``uniform`` fleet carved into 3 regions via ``FLConfig.regions``, so
    cohort shapes are stable round to round and the comparison isolates
    call-count, not jit-cache churn or bucket fragmentation."""
    from repro.data import FederatedData, iid_partition, \
        make_classification_data
    from repro.fl import FLConfig, FLServer, MLPTask, build_policy

    n_devices = 30
    train, test = make_classification_data(n_samples=128 * n_devices, seed=0)
    parts = iid_partition(len(train.y), n_devices, seed=0, size_skew=0.0)
    data = FederatedData(train, test, parts)
    task = MLPTask(dim=32, hidden=32, n_classes=10)

    rows = []
    for k in ks:
        per_round = {}
        for region_exec in ("sequential", "stacked"):
            cfg = FLConfig(n_devices=n_devices, k_select=k, rounds=rounds,
                           l_ep=l_ep, lr=0.1, seed=0, executor="vmapped",
                           regions=3, region_exec=region_exec)
            srv = FLServer(cfg, task, data)
            policy = build_policy("fedavg")
            srv.run_round(policy)              # warmup: jit compile
            t0 = time.perf_counter()
            for _ in range(rounds):
                srv.run_round(policy)
            per_round[region_exec] = (time.perf_counter() - t0) / rounds
        row = {"bench": "region_exec", "n_devices": n_devices, "k": k,
               "n_regions": 3, "l_ep": l_ep,
               "sequential_round_s": round(per_round["sequential"], 4),
               "stacked_round_s": round(per_round["stacked"], 4),
               "speedup": round(per_round["sequential"]
                                / per_round["stacked"], 2)}
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    return rows


def main() -> None:
    # allow_abbrev=False keeps argparse in sync with the literal sys.argv
    # check above that decides the XLA device-count flag
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None, help="run a single pair")
    ap.add_argument("--fl-executors", action="store_true",
                    help="time sequential vs vmapped FL round execution "
                         "instead of the HLO dry-run iterations")
    ap.add_argument("--fl-modes", action="store_true",
                    help="compare sync vs async round regimes on simulated "
                         "wall-clock-to-accuracy per scenario")
    ap.add_argument("--quick", action="store_true",
                    help="shrink --fl-modes / --fleet to a CI smoke (one "
                         "size per bench; asserts the batched async loop "
                         "beats the sequential oracle at 100k devices)")
    ap.add_argument("--fleet", action="store_true",
                    help="time the vectorized DevicePool against the seed "
                         "per-object fleet at 10k/100k devices, plus "
                         "region-vectorized vs sequential-region hierarchical "
                         "round execution")
    args = ap.parse_args()
    if args.fl_modes:
        out = args.out or "results/fl_modes.json"
        results = run_fl_modes_bench(quick=args.quick)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        return
    if args.fleet:
        out = args.out or "results/fleet_scale.json"
        if args.quick:                       # CI smoke: one size per bench
            results = run_fleet_bench(sizes=(10_000,))
            results += run_region_exec_bench(ks=(6,))
            results += run_selection_bench(sizes=(10_000,))
        else:
            results = run_fleet_bench()
            results += run_region_exec_bench()
            results += run_selection_bench()
        results += run_async_step_bench(quick=args.quick)
        from repro.obs import run_manifest

        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            # rows are wall-clock timings: the manifest (platform, package
            # versions, backend) is what makes them comparable across runs
            json.dump({"manifest": run_manifest(
                           extra={"driver": "perf_iterations",
                                  "quick": args.quick}),
                       "rows": results}, f, indent=1)
        return
    if args.fl_executors:
        out = args.out or "results/fl_executors.json"
        results = run_fl_executor_bench()
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        return
    args.out = args.out or "results/perf_iterations.json"

    from repro.launch.dryrun import run_one  # noqa: F401 (after XLA_FLAGS)

    results = []
    for pair, arch, shape, it_name, kwargs in EXPERIMENTS:
        if args.only and pair != args.only:
            continue
        kwargs = _apply_special_overrides(dict(kwargs), arch)
        rec = run_one(arch, shape, **kwargs)
        summ = _summ(rec)
        entry = {"pair": pair, "arch": arch, "shape": shape,
                 "iteration": it_name, **summ}
        results.append(entry)
        print(json.dumps(entry), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
