"""End-to-end FedRank driver: imitation learning -> online FL with every
baseline, time/energy-to-accuracy report — the paper's full pipeline.

Supports any assigned architecture as the *global model* via --arch
(reduced variant trains as a tiny LM across clients), or the default MLP
classification task (the paper's vision-task stand-in).

    PYTHONPATH=src python examples/fl_end_to_end.py --rounds 25
    PYTHONPATH=src python examples/fl_end_to_end.py --arch rwkv6-3b --rounds 8

NOTE: --arch mode trains a (reduced) transformer on every client — minutes
per round on CPU (the code path itself is unit-tested fast in
tests/test_fl.py::test_lm_task_fl_round). The MLP default runs 25 rounds in
about a minute.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_model_config
from repro.core import (
    augment_demonstrations,
    collect_demonstrations,
    pretrain_qnet,
)
from repro.data import (
    FederatedData,
    SyntheticClassificationDataset,
    dirichlet_partition,
    make_classification_data,
    make_lm_stream,
)
from repro.fl import FLConfig, FLServer, LMTask, MLPTask, available_executors, \
    available_scenarios, build_policy
from repro.launch.compile_cache import enable_compile_cache

POLICY_NAMES = ("fedavg", "afl", "tifl", "oort", "favor", "fedmarl", "fedrank")


def build_lm_fl_data(cfg, n_clients: int, seq: int = 32, seed: int = 0):
    """Synthetic LM federated data: sequences as 'samples', token-histogram
    Dirichlet partition for heterogeneity."""
    stream = make_lm_stream(n_tokens=120_000, vocab=cfg.vocab_size, seed=seed)
    n_seq = len(stream) // (seq + 1)
    x = np.stack([stream[i * (seq + 1):(i + 1) * (seq + 1) - 1] for i in range(n_seq)])
    y = np.stack([stream[i * (seq + 1) + 1:(i + 1) * (seq + 1)] for i in range(n_seq)])
    # heterogeneity: partition by dominant leading token bucket
    labels = (x[:, 0] % 10).astype(np.int64)
    parts = dirichlet_partition(labels, n_clients, 0.3, seed=seed)
    train = SyntheticClassificationDataset(x, y[:, 0], 10)  # container reuse
    train.x, train.y = x, y          # LM pairs: x tokens, y shifted tokens
    test = SyntheticClassificationDataset(x[:200], y[:200, 0], 10)
    test.x, test.y = x[:200], y[:200]
    return FederatedData(train, test, parts)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--devices", type=int, default=40)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--sigma", type=float, default=0.1)
    ap.add_argument("--arch", default=None,
                    help="use a reduced assigned arch as the FL global model")
    ap.add_argument("--executor", default="sequential",
                    choices=available_executors(),
                    help="client executor: 'vmapped' runs each cohort as one "
                         "jitted step")
    ap.add_argument("--scenario", default="uniform",
                    choices=available_scenarios(),
                    help="fleet environment: tier mix, load dynamics, "
                         "availability and failures (repro.fl.scenarios)")
    ap.add_argument("--mode", default="sync", choices=("sync", "async"),
                    help="round regime: synchronous barrier rounds, or "
                         "asynchronous buffered aggregation (3x-K "
                         "concurrency, polynomial staleness weighting; "
                         "repro.fl.async_engine)")
    args = ap.parse_args()

    if args.arch:
        cfg = get_model_config(args.arch, smoke=True)
        task = LMTask(cfg, seq_len=32)
        data = build_lm_fl_data(cfg, args.devices)
        lr = 0.5
    else:
        train, test = make_classification_data(n_samples=12000, seed=0)
        parts = dirichlet_partition(train.y, args.devices, args.sigma, seed=0)
        data = FederatedData(train, test, parts)
        task = MLPTask(dim=32, hidden=64, n_classes=10)
        lr = 0.1

    async_kw = ({"mode": "async", "async_concurrency": 3 * args.k,
                 "staleness": "polynomial"} if args.mode == "async" else {})

    def make_server(seed=1, **overrides):
        kw = {**async_kw, **overrides}
        return FLServer(FLConfig(n_devices=args.devices, k_select=args.k,
                                 rounds=args.rounds, l_ep=3, lr=lr, seed=seed,
                                 executor=args.executor,
                                 scenario=args.scenario, **kw),
                        task, data)

    print("== collecting expert demonstrations (Alg. 1) ==")
    # IL demonstrations are always collected synchronously (the experts'
    # teacher signal is a full-round cohort); only online FL honors --mode
    demos = collect_demonstrations(lambda seed=1: make_server(seed, mode="sync"),
                                   rounds_per_expert=8)
    demos = augment_demonstrations(demos, n_synthetic=150)
    qnet, il = pretrain_qnet(demos, steps=800)
    print(f"IL: {len(demos)} demos, ranking acc {il['rank_acc'][-1]:.3f}, "
          f"top-10 overlap {il['top10_overlap'][-1]:.3f}")

    print("\n== online FL: all selection policies ==")
    results = {}
    for name in POLICY_NAMES:
        kw = {"qnet": qnet, "k": args.k} if name == "fedrank" else {}
        pol = build_policy(name, **kw)
        hist = make_server().run(pol)
        results[pol.name] = hist
        print(f"{pol.name:10s} acc={hist[-1].acc:.4f} "
              f"T={hist[-1].cum_time:8.1f}s E={hist[-1].cum_energy:9.1f}J")

    base = results["fedavg"]
    target = 0.95 * base[-1].acc
    print(f"\n== time/energy to {target:.3f} accuracy (95% of FedAvg final) ==")
    for name, hist in results.items():
        hit = next((r for r in hist if r.acc >= target), None)
        if hit:
            print(f"{name:10s} ToA={hit.cum_time:8.1f}s EoA={hit.cum_energy:9.1f}J "
                  f"(round {hit.round})")
        else:
            print(f"{name:10s} did not reach target")


if __name__ == "__main__":
    main()
