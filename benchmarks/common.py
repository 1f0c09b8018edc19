"""Shared benchmark harness: FL environment builders + CSV emit helpers."""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.data import FederatedData, dirichlet_partition, iid_partition, \
    make_classification_data
from repro.fl import FLConfig, FLServer, MLPTask
from repro.launch.compile_cache import enable_compile_cache


def build_env(n_devices: int = 40, k: int = 5, rounds: int = 25, l_ep: int = 3,
              sigma: Optional[float] = 0.1, n_samples: int = 12000,
              seed: int = 0, prox_mu: float = 0.0,
              alpha: float = 2.0, beta: float = 2.0,
              executor: str = "sequential", scenario: str = "uniform",
              mode: str = "sync", async_concurrency: int = 0,
              staleness: str = "constant", buffer_size: int = 0,
              feature_set: str = "paper6", aggregator: str = "mean",
              agg_trim: int = 1, agg_f: int = 1, agg_m: int = 0,
              observe=None):
    """Returns (make_server, task, data). sigma=None -> IID.  ``scenario``
    names the fleet environment (see repro.fl.scenarios); ``mode="async"``
    selects the buffered asynchronous engine (repro.fl.async_engine) with
    the given concurrency/staleness knobs; ``feature_set`` shapes
    ``RoundContext.probe_states`` (repro.core.features); ``aggregator``
    picks the (robust) merge with its trim/f/m_select knobs
    (repro.fl.aggregation) — the adversarial-scenario sweeps pair it with
    the attack scenarios of repro.fl.attacks; ``observe`` is the
    ``FLConfig.observe`` recorder spec (``make_server`` accepts a per-run
    override, so sweep drivers can trace each run to its own directory)."""
    enable_compile_cache()
    train, test = make_classification_data(n_samples=n_samples, seed=seed)
    if sigma is None:
        parts = iid_partition(len(train.y), n_devices, seed=seed, size_skew=0.8)
    else:
        parts = dirichlet_partition(train.y, n_devices, sigma, seed=seed)
    data = FederatedData(train, test, parts)
    task = MLPTask(dim=32, hidden=64, n_classes=10)

    def make_server(run_seed: int = 1, observe=observe) -> FLServer:
        cfg = FLConfig(n_devices=n_devices, k_select=k, rounds=rounds,
                       l_ep=l_ep, lr=0.1, seed=run_seed, prox_mu=prox_mu,
                       alpha=alpha, beta=beta, executor=executor,
                       scenario=scenario, mode=mode,
                       async_concurrency=async_concurrency,
                       staleness=staleness, buffer_size=buffer_size,
                       feature_set=feature_set, aggregator=aggregator,
                       agg_trim=agg_trim, agg_f=agg_f, agg_m=agg_m,
                       observe=observe)
        return FLServer(cfg, task, data)

    return make_server, task, data


def time_to_accuracy(history, target: float):
    """(cum_time, cum_energy, round) at which target accuracy is reached."""
    for r in history:
        if r.acc >= target:
            return r.cum_time, r.cum_energy, r.round
    return None, None, None


def emit_csv(rows: List[Dict], header: List[str]) -> None:
    print(",".join(header))
    for row in rows:
        print(",".join(str(row.get(h, "")) for h in header))


def run_policy(make_server, policy, rounds: Optional[int] = None):
    srv = make_server()
    t0 = time.time()
    hist = srv.run(policy, rounds=rounds)
    return hist, time.time() - t0
