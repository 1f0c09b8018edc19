"""Quickstart: FedRank client selection in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Policies are built by name from the registry (``repro.fl.build_policy``);
the fleet environment by name from the scenario registry
(``FLConfig.scenario`` -> ``repro.fl.build_scenario``); the round engine is
selected via ``FLConfig.executor`` — "sequential" is the per-client
reference loop, "vmapped" runs each cohort as one jitted step — and the
round *regime* via ``FLConfig.mode``: "sync" barrier rounds, or "async"
buffered staleness-weighted aggregation that trains through availability
gaps (docs/architecture.md).
"""
from repro.core import augment_demonstrations, collect_demonstrations, pretrain_qnet
from repro.data import FederatedData, dirichlet_partition, make_classification_data
from repro.fl import FLConfig, FLServer, MLPTask, build_policy
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. a federated dataset: 30 clients, Dirichlet(0.1) non-IID labels
train, test = make_classification_data(n_samples=8000, seed=0)
data = FederatedData(train, test, dirichlet_partition(train.y, 30, 0.1, seed=0))
task = MLPTask(dim=32, hidden=64, n_classes=10)

make_server = lambda seed=1: FLServer(
    FLConfig(n_devices=30, k_select=5, rounds=15, l_ep=3, lr=0.1, seed=seed,
             scenario="cellular-tail",  # low-end-heavy fleet, dropout + deadline
             executor="vmapped"),   # cohort-parallel rounds; "sequential" = reference
    task, data)

# 2. imitation-learning pre-training against the analytical experts
demos = collect_demonstrations(make_server, rounds_per_expert=6)
qnet, il_hist = pretrain_qnet(augment_demonstrations(demos, 100), steps=600)
print(f"IL pretrain: pairwise ranking accuracy -> {il_hist['rank_acc'][-1]:.3f}")

# 3. run FL with FedRank vs random selection (policies built by name)
for policy in (build_policy("fedavg"), build_policy("fedrank", qnet=qnet, k=5)):
    hist = make_server().run(policy)
    print(f"{policy.name:8s} acc {hist[0].acc:.3f} -> {hist[-1].acc:.3f}   "
          f"time {hist[-1].cum_time:7.1f}s   energy {hist[-1].cum_energy:7.1f}J")

# 4. same fleet, asynchronous regime: dispatch on arrival, aggregate every
#    buffer_size uploads with polynomial staleness weighting — cum_time is
#    the virtual clock over overlapping client work, not a sum of barriers
srv = FLServer(FLConfig(n_devices=30, k_select=5, rounds=15, l_ep=3, lr=0.1,
                        seed=1, scenario="cellular-tail", executor="vmapped",
                        mode="async", async_concurrency=15,
                        staleness="polynomial"), task, data)
hist = srv.run(build_policy("fedrank", qnet=qnet, k=5))
print(f"fedrank (async) acc {hist[0].acc:.3f} -> {hist[-1].acc:.3f}   "
      f"time {hist[-1].cum_time:7.1f}s   energy {hist[-1].cum_energy:7.1f}J")
