"""Round-engine API: RoundPlan construction, the policy registry, and the
executor registry."""
import jax
import numpy as np
import pytest

from repro.fl import FLConfig, FLServer, available_executors, available_policies, \
    build_policy, make_executor
from repro.fl.engine import RoundPlan, build_round_plan


def test_registry_round_trip_all_policies():
    """Every registered name builds a policy satisfying the SelectionPolicy
    protocol (name, needs_probing, probe_set/select/observe)."""
    names = available_policies()
    assert {"fedavg", "fedprox", "afl", "tifl", "oort", "favor", "fedmarl",
            "fedrank", "fedrank-I", "fedrank-P", "fedrank-IP"} <= set(names)
    for name in names:
        pol = build_policy(name)
        assert isinstance(pol.name, str) and pol.name
        assert isinstance(pol.needs_probing, bool) or pol.needs_probing in (0, 1)
        for attr in ("probe_set", "select", "observe"):
            assert callable(getattr(pol, attr)), f"{name} lacks {attr}"


def test_registry_kwargs_and_unknown_name():
    pol = build_policy("fedrank", k=7, seed=3)
    assert pol.name == "fedrank"
    assert build_policy("fedrank-P").rank_eps == 0.0
    with pytest.raises(KeyError, match="unknown policy"):
        build_policy("nope")


def test_executor_registry():
    assert {"sequential", "vmapped"} <= set(available_executors())
    assert make_executor("sequential").name == "sequential"
    assert make_executor("vmapped").name == "vmapped"
    with pytest.raises(KeyError, match="unknown executor"):
        make_executor("nope")


def test_round_plan_shapes(mlp_task, fl_data):
    """Probing policies plan probe(1) -> complete(l_ep-1); non-probing plan
    an empty probe stage and complete all l_ep epochs."""
    cfg = FLConfig(n_devices=20, k_select=4, rounds=1, l_ep=3, seed=0)
    srv = FLServer(cfg, mlp_task, fl_data)
    ctx = srv._ctx()

    plan = build_round_plan(build_policy("fedavg"), ctx, cfg.l_ep)
    assert not plan.has_probe and len(plan.probe_ids) == 0
    assert plan.probe_epochs == 0 and plan.completion_epochs == 3

    plan = build_round_plan(build_policy("fedmarl"), ctx, cfg.l_ep)
    assert plan.has_probe and len(plan.probe_ids) >= cfg.k_select
    assert plan.probe_epochs == 1 and plan.completion_epochs == 2


def test_policy_can_emit_custom_plan(mlp_task, fl_data):
    """A policy may bypass the needs_probing adapter and emit its own plan
    (e.g. a wider probe pool) — the server executes it unchanged."""
    from repro.core import RandomPolicy

    class WideProbe(RandomPolicy):
        needs_probing = True

        def plan_round(self, ctx, l_ep):
            return RoundPlan(np.arange(ctx.n, dtype=np.int64),
                             probe_epochs=1, completion_epochs=l_ep - 1)

        def select(self, ctx, probe_ids, probe_states):
            assert probe_ids is not None and len(probe_ids) == ctx.n
            return probe_ids[np.argsort(probe_states[:, 4])[:ctx.k]]

    cfg = FLConfig(n_devices=12, k_select=3, rounds=2, l_ep=2, lr=0.1, seed=0)
    srv = FLServer(cfg, mlp_task, fl_data)
    hist = srv.run(WideProbe())
    for r in hist:
        assert len(r.probe_set) == 12
        assert set(r.selected).issubset(set(r.probe_set.tolist()))


def test_stale_loss_uses_most_recent_epoch(mlp_task, fl_data):
    """Both probing and non-probing paths record the LAST local-epoch loss
    (the freshest signal), not the first."""
    from repro.fl.client import local_train

    cfg = FLConfig(n_devices=10, k_select=3, rounds=1, l_ep=3, lr=0.1, seed=0)
    srv = FLServer(cfg, mlp_task, fl_data)
    res = srv.run_round(build_policy("fedavg"))
    for i in res.selected:
        i = int(i)
        idx = fl_data.client_indices[i]
        _, losses = local_train(
            mlp_task, srv.task.init(jax.random.PRNGKey(cfg.seed)),
            fl_data.train.x[idx], fl_data.train.y[idx], epochs=cfg.l_ep,
            lr=cfg.lr, batch_size=cfg.local_batch,
            seed=cfg.seed + 2000 * 0 + i)
        assert srv.last_loss[i] == pytest.approx(float(losses[-1]), rel=1e-5)


def test_random_policy_name_distinct():
    assert build_policy("random").name == "random"
    assert build_policy("fedavg").name == "fedavg"


def test_vmapped_executor_with_mesh_matches_sequential(mlp_task, fl_data):
    """Mesh-backed VmappedExecutor (1-device host mesh, clients on 'data')
    still matches the sequential reference."""
    from repro.fl.engine import SequentialExecutor, VmappedExecutor, build_requests
    from repro.launch.mesh import make_host_mesh

    gp = mlp_task.init(jax.random.PRNGKey(0))
    reqs = build_requests(range(3), fl_data, 2, seed=0, round_idx=0, stride=0)
    kw = dict(lr=0.1, batch_size=32, prox_mu=0.0)
    seq = SequentialExecutor().run(mlp_task, gp, reqs, **kw)
    par = VmappedExecutor(mesh=make_host_mesh()).run(mlp_task, gp, reqs, **kw)
    for c in seq.params:
        np.testing.assert_allclose(seq.losses[c], par.losses[c],
                                   atol=1e-5, rtol=1e-4)
        for la, lb in zip(jax.tree.leaves(seq.params[c]),
                          jax.tree.leaves(par.params[c])):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-5, rtol=1e-4)
