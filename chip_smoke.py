#!/usr/bin/env python3
"""Bring-up smoke of the FedRank FL round on a TPU chip.

    python chip_smoke.py [--seed 0]             # one chip: every phase
    python chip_smoke.py --chips 4 [--seed 0]   # the mesh-sharded round only

Drives the system through the entry points a user calls (``pretrain_qnet``,
``build_policy``, ``FLServer.run``) at fixed sizes, with every datum made
from ``--seed``.  One process holds the chip and runs every phase:

* device     -- the platform must be ``tpu`` and all three FL kernels must
                resolve to their compiled Pallas implementations;
* IL         -- expert demonstrations from a small sync server, then
                ``pretrain_qnet`` through the compiled ``pairwise_rank``;
* sync       -- FedRank on a 100,000-device ``trace-synthetic-week`` fleet
                (probe/select rank the fleet through ``select_topk``);
* async      -- buffered async on the same fleet (the virtual clock steps
                through ``fleet_state``);
* oracles    -- each kernel against its reference at the sizes above, and
                one vmapped sync round against the sequential executor.

``--chips 4`` runs one sync round with the vmapped executor sharded over a
``(4, 1)`` ``("data", "model")`` mesh and the same round unsharded on
device 0, and nothing else.

Timing lines are host wall seconds fenced by ``block_until_ready``; compile
seconds (trace + lower + backend compile, from ``jax.monitoring``) are
reported beside them.  Every line names the device.  The last line of
standard output is the JSON result, printed only when every phase ran and
every check passed; otherwise the exit code is non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# ---- fixed sizes ----------------------------------------------------------
FLEET = 100_000                 # devices in the fleet
SAMPLES_PER_CLIENT = 8          # iid client shards of the synthetic task
TEST_ROWS = 10_000              # server-side evaluation set
SCENARIO = "trace-synthetic-week"
L_EP, LR = 2, 0.1               # local epochs (probe 1 + complete 1), SGD lr
SYNC_K, SYNC_ROUNDS = 32, 3
PROBE_K = 52                    # FedRank.probe_set's fused cut at K=32:
#                                 min(MAX_COHORT=64, 2.5 K) minus 64 // 5
#                                 explorers
SELECT_N = 64                   # FedRank.select orders the whole probe cohort
ASYNC_K, ASYNC_CONCURRENCY, ASYNC_AGGS = 512, 8192, 3
IL_FLEET, IL_K = 200, 10        # demonstration server; experts probe 3 K
IL_ROUNDS_PER_EXPERT = 4
IL_SYNTHETIC = 52               # synthetic expert cohorts of 3 K = 30 rows
IL_STEPS, IL_BATCH = 200, 16
MESH_CHIPS = 4

# ---- tolerances -----------------------------------------------------------
# pairwise_rank: the kernel sums 128x128 tiles where XLA sums the whole
# matrix, and Mosaic's exp/log1p may differ from XLA's by a few ulp
RANK_RTOL = 1e-4
# executor parity: both executors run the MLP at TPU default matmul
# precision (bf16 operands); a one-ulp bf16 flip of one of 128 hidden
# activations moves an output by ~3e-5 relative, and SGD carries it
PARAM_ATOL, PARAM_RTOL = 1e-4, 1e-3

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Run:
    """Device label, compile clock, and the list of failed checks."""

    def __init__(self, device: dict):
        import jax

        self.device = device
        self.compile_s = 0.0
        self.failures: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def timed(self, fn):
        """``(out, wall_s, compile_s)`` of ``fn()``, fenced on its output."""
        import jax

        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0, self.compile_s - c0

    def report(self, name: str, **numbers):
        dev = self.device
        fields = " ".join(f"{k}={v}" for k, v in numbers.items())
        print(f"{name}: {fields} device_kind={dev['kind']!r} "
              f"platform={dev['platform']} count={dev['count']}", flush=True)

    def check(self, name: str, ok: bool, detail: str):
        print(f"check {name}: {'ok' if ok else 'FAIL'} ({detail})", flush=True)
        if not ok:
            self.failures.append(name)


# ---------------------------------------------------------------------------
def device_phase(chips: int) -> dict:
    import jax

    from repro.kernels.fleet_state.ops import resolve_fleet_state_impl
    from repro.kernels.pairwise_rank.ops import resolve_rank_impl
    from repro.kernels.select_topk.ops import resolve_select_impl

    devices = jax.devices()
    d0 = devices[0]
    print(f"devices: {devices}", flush=True)
    print(f"platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {d0.platform!r}, not 'tpu'; "
                 "this smoke runs only on the chip")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {len(devices)}")
    impls = {"select_topk": resolve_select_impl(),
             "fleet_state": resolve_fleet_state_impl(),
             "pairwise_rank": resolve_rank_impl()}
    print(f"kernel impls: {impls}", flush=True)
    if set(impls.values()) != {"pallas"}:
        sys.exit(f"chip_smoke: kernels must resolve to 'pallas' on TPU: "
                 f"{impls}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def fleet_data(seed: int):
    from repro.data import FederatedData, iid_partition, \
        make_classification_data

    train, test = make_classification_data(
        n_samples=FLEET * SAMPLES_PER_CLIENT, seed=seed)
    test = type(test)(test.x[:TEST_ROWS], test.y[:TEST_ROWS], test.n_classes)
    return FederatedData(train, test,
                         iid_partition(len(train.y), FLEET, seed=seed))


def sync_server(data, seed: int, executor="vmapped"):
    """The sync FedRank fleet server; ``executor`` is a registry name or
    an executor instance."""
    from repro.fl import FLConfig, FLServer, MLPTask

    named = isinstance(executor, str)
    cfg = FLConfig(n_devices=FLEET, k_select=SYNC_K, rounds=SYNC_ROUNDS,
                   l_ep=L_EP, lr=LR, seed=seed, scenario=SCENARIO,
                   executor=executor if named else executor.name)
    return FLServer(cfg, MLPTask(), data,
                    executor=None if named else executor)


def run_server(srv, policy, rounds=None):
    """``FLServer.run``; returns the global params to fence the timing on."""
    srv.run(policy, rounds=rounds)
    return srv.global_params


def host_params(params):
    import jax
    import numpy as np

    return [np.asarray(leaf) for leaf in jax.tree.leaves(params)]


def check_round_parity(run: Run, name: str, a, b):
    """Same cohorts, params within (PARAM_ATOL, PARAM_RTOL)."""
    import numpy as np

    ra, rb = a.history[0], b.history[0]
    same = (np.array_equal(ra.probe_set, rb.probe_set)
            and np.array_equal(ra.selected, rb.selected))
    head = lambda r: sorted(r.selected.tolist())[:6]
    run.check(f"{name}_cohorts", same,
              f"probe {len(ra.probe_set)}, selected {head(ra)}... "
              f"vs {head(rb)}...")
    pa, pb = host_params(a.global_params), host_params(b.global_params)
    diff = max(float(np.max(np.abs(x - y))) for x, y in zip(pa, pb))
    close = all(np.allclose(x, y, atol=PARAM_ATOL, rtol=PARAM_RTOL)
                for x, y in zip(pa, pb))
    run.check(f"{name}_params", close,
              f"max |diff| {diff} (atol {PARAM_ATOL}, rtol {PARAM_RTOL})")
    finite = all(np.isfinite(x).all() for x in pa + pb)
    run.check(f"{name}_finite", finite, "global params finite")


# ---------------------------------------------------------------------------
def il_phase(run: Run, seed: int):
    """Demonstrations from a small sync server, then IL pretraining through
    the compiled pairwise_rank kernel."""
    from repro.core import augment_demonstrations, collect_demonstrations, \
        pretrain_qnet
    from repro.data import FederatedData, iid_partition, \
        make_classification_data
    from repro.fl import FLConfig, FLServer, MLPTask

    train, test = make_classification_data(n_samples=IL_FLEET * 40, seed=seed)
    data = FederatedData(train, test,
                         iid_partition(len(train.y), IL_FLEET, seed=seed))
    task = MLPTask()

    def make_server():
        return FLServer(FLConfig(n_devices=IL_FLEET, k_select=IL_K,
                                 rounds=IL_ROUNDS_PER_EXPERT, l_ep=L_EP,
                                 lr=LR, seed=seed, executor="vmapped"),
                        task, data)

    demos, wall, comp = run.timed(lambda: collect_demonstrations(
        make_server, rounds_per_expert=IL_ROUNDS_PER_EXPERT))
    run.report("il_collect", demos=len(demos), wall_s=wall, compile_s=comp)
    demos = augment_demonstrations(demos, n_synthetic=IL_SYNTHETIC,
                                   cohort=3 * IL_K, seed=seed)
    (q, hist), wall, comp = run.timed(lambda: pretrain_qnet(
        demos, seed=seed, steps=IL_STEPS, batch=IL_BATCH))
    run.report("il_pretrain", steps=IL_STEPS, batch=IL_BATCH,
               wall_s=wall, compile_s=comp, step_s=wall / IL_STEPS,
               loss_first=hist["loss"][0], loss_last=hist["loss"][-1],
               rank_acc=hist["rank_acc"][-1])
    # one loss value: step 0 sees the same init and batch on both paths
    _, ref = pretrain_qnet(demos, seed=seed, steps=1, batch=IL_BATCH,
                           rank_impl="xla")
    a, b = hist["loss"][0], ref["loss"][0]
    run.check("il_loss_pallas_vs_xla", abs(a - b) <= RANK_RTOL * abs(b),
              f"{a} vs {b}, rtol {RANK_RTOL}")
    return q, demos


def sync_phase(run: Run, seed: int, data, q):
    from repro.fl import build_policy

    srv = sync_server(data, seed)
    policy = build_policy("fedrank", qnet=q, k=SYNC_K, seed=seed)
    for r in range(SYNC_ROUNDS):
        _, wall, comp = run.timed(lambda: run_server(srv, policy, rounds=1))
        res = srv.history[-1]
        run.report("sync_round", round=r, wall_s=wall, compile_s=comp,
                   selected=len(res.selected), probed=len(res.probe_set),
                   acc=res.acc)


def sync_parity_phase(run: Run, seed: int, data, q):
    """One vmapped round vs the same round on the sequential executor."""
    from repro.fl import build_policy

    servers = {}
    for ex in ("vmapped", "sequential"):
        srv = sync_server(data, seed, executor=ex)
        policy = build_policy("fedrank", qnet=q, k=SYNC_K, seed=seed)
        _, wall, comp = run.timed(lambda: run_server(srv, policy, rounds=1))
        run.report(f"parity_round[{ex}]", wall_s=wall, compile_s=comp)
        servers[ex] = srv
    check_round_parity(run, "vmapped_vs_sequential", servers["vmapped"],
                       servers["sequential"])


def async_phase(run: Run, seed: int, data):
    import numpy as np

    from repro.fl import FLConfig, FLServer, MLPTask, build_policy

    cfg = FLConfig(n_devices=FLEET, k_select=ASYNC_K, rounds=ASYNC_AGGS,
                   l_ep=L_EP, lr=LR, seed=seed, scenario=SCENARIO,
                   mode="async", async_concurrency=ASYNC_CONCURRENCY,
                   staleness="polynomial", executor="vmapped")
    srv = FLServer(cfg, MLPTask(), data)
    _, wall, comp = run.timed(lambda: run_server(srv, build_policy("fedavg")))
    hist = srv.history
    run.report("async", aggregations=len(hist), wall_s=wall, compile_s=comp,
               wall_per_agg_s=wall / len(hist),
               host_s_per_agg=[h.host_time_s for h in hist],
               acc=hist[-1].acc)
    run.check("async_aggregations", len(hist) == ASYNC_AGGS and all(
        np.isfinite(x).all() for x in host_params(srv.global_params)),
        f"{len(hist)} aggregations, params finite")
    return srv


def kernel_oracle_phase(run: Run, seed: int, q, demos, srv):
    """Each kernel vs its reference on the chip, at the sizes used above."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.features import get_feature_set
    from repro.core.qnet import apply_qnet
    from repro.kernels.fleet_state.kernel import segment_index_pallas
    from repro.kernels.fleet_state.ops import segment_index
    from repro.kernels.pairwise_rank.kernel import pairwise_rank_pallas
    from repro.kernels.pairwise_rank.ops import pairwise_rank
    from repro.kernels.select_topk.kernel import select_topk_pallas
    from repro.kernels.select_topk.ops import select_topk

    def check_compiled(name, jitted, *args, **static):
        """The executable holds the Mosaic custom call: the kernel was
        compiled, not interpreted."""
        text = jitted.lower(*args, **static).compile().as_text()
        run.check(f"{name}_compiled", "tpu_custom_call" in text,
                  "Mosaic custom call in the executable")

    rng = np.random.default_rng([seed, 11])
    # select_topk: the fleet-wide probe cut and the full probe-cohort order
    for n, k, masked in ((FLEET, PROBE_K, True), (SELECT_N, SELECT_N, False)):
        feats = rng.normal(size=(n, 6)).astype(np.float32)
        mask = rng.random(n) < 0.7 if masked else None
        bias = -0.05 * np.sqrt(rng.integers(0, 4, n)).astype(np.float32)
        ip, vp = select_topk(q, feats, mask, k, bias=bias, impl="pallas")
        ix, vx = select_topk(q, feats, mask, k, bias=bias, impl="xla")
        run.check(f"select_topk[n={n},k={k}]",
                  np.array_equal(ip, ix) and len(ip) == k,
                  f"{len(ip)} indices, max |score diff| "
                  f"{float(np.max(np.abs(vp - vx)))}")
        check_compiled(f"select_topk[n={n}]", select_topk_pallas, q,
                       jnp.asarray(feats), jnp.ones(n), jnp.zeros(n), k=k)

    # fleet_state: the async fleet's own trace, queried at several instants
    fleet = srv.pool.availability.fleet
    tr = fleet.trace
    for t in (0.0, 3.7e4, 2.9e5):
        args = (tr._seg_key, tr._seg_dev, tr.t_start, tr.period_s, fleet.src,
                t + fleet.phase_s)
        ip = segment_index(*args, impl="pallas")
        ix = segment_index(*args, impl="numpy")
        run.check(f"fleet_state[t={t}]", np.array_equal(ip, ix),
                  f"N={len(ip)} S={tr.n_segments}, "
                  f"{int(np.sum(ip != ix))} mismatches")
    seg = lambda dt: jax.ShapeDtypeStruct((tr.n_segments,), dt)
    query = lambda dt: jax.ShapeDtypeStruct((FLEET,), dt)
    check_compiled("fleet_state", segment_index_pallas, seg(jnp.int32),
                   seg(jnp.int32), seg(jnp.float32), query(jnp.int32),
                   query(jnp.int32), query(jnp.float32))

    # pairwise_rank: one IL batch of expert cohorts scored by the Q-net
    fs = get_feature_set("paper6")
    batch = [d for d in demos if len(d.states) == 3 * IL_K][:IL_BATCH]
    feats = jnp.asarray(np.stack([fs.featurize(d.states) for d in batch]))
    tgts = jnp.asarray(np.stack([(d.scores - d.scores.mean())
                                 / (d.scores.std() + 1e-9) for d in batch]),
                       jnp.float32)
    masks = jnp.ones(tgts.shape, jnp.float32)
    scores = jax.vmap(apply_qnet, (None, 0))(q, feats)

    def rank(impl):
        return np.asarray(jax.vmap(lambda s, t, m: pairwise_rank(
            s, t, m, impl=impl, hard=True))(scores, tgts, masks))

    lp, lx = rank("pallas"), rank("xla")
    run.check(f"pairwise_rank[batch={len(batch)},n={3 * IL_K}]",
              bool(np.allclose(lp, lx, rtol=RANK_RTOL, atol=0.0)),
              f"max rel diff {float(np.max(np.abs(lp - lx) / np.abs(lx)))},"
              f" rtol {RANK_RTOL}")
    check_compiled("pairwise_rank", jax.jit(jax.vmap(
        lambda s, t, m: pairwise_rank_pallas(s, t, m, hard=True))),
        scores, tgts, masks)


def mesh_phase(run: Run, seed: int, data):
    """One sync round sharded over a (4, 1) mesh vs unsharded on device 0."""
    from repro.fl import build_policy
    from repro.fl.engine import VmappedExecutor
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(MESH_CHIPS)
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.ravel().tolist()}",
          flush=True)
    servers = {}
    for name, ex in (("sharded", VmappedExecutor(mesh=mesh)),
                     ("device0", VmappedExecutor())):
        srv = sync_server(data, seed, executor=ex)
        policy = build_policy("fedrank", k=SYNC_K, seed=seed)
        _, wall, comp = run.timed(lambda: run_server(srv, policy, rounds=1))
        run.report(f"mesh_round[{name}]", wall_s=wall, compile_s=comp,
                   selected=len(srv.history[0].selected))
        servers[name] = srv
    check_round_parity(run, "sharded_vs_device0", servers["sharded"],
                       servers["device0"])


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"{MESH_CHIPS}: run only the mesh-sharded round and "
                         "its unsharded comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_phase(args.chips)
    run = Run(device)
    t0 = time.perf_counter()
    data = fleet_data(args.seed)
    run.report("fleet_data", devices=FLEET, samples=len(data.train.y),
               build_s=time.perf_counter() - t0)
    if args.chips == MESH_CHIPS:
        mesh_phase(run, args.seed, data)
    else:
        q, demos = il_phase(run, args.seed)
        sync_phase(run, args.seed, data, q)
        async_srv = async_phase(run, args.seed, data)
        kernel_oracle_phase(run, args.seed, q, demos, async_srv)
        sync_parity_phase(run, args.seed, data, q)

    import jax
    stats = jax.devices()[0].memory_stats() or {}
    run.report("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
               total_wall_s=time.perf_counter() - t0,
               total_compile_s=run.compile_s)
    if run.failures:
        print(f"chip_smoke: {len(run.failures)} check(s) failed: "
              f"{run.failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
