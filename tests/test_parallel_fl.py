"""Pod-scale FL simulation path: K clients' local training as one vmapped
(pjit-able) step — results must match the sequential per-client loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.client import local_train, make_parallel_local_train


def test_parallel_local_train_matches_sequential(mlp_task, fl_data):
    key = jax.random.PRNGKey(0)
    global_params = mlp_task.init(key)
    k_clients = 4
    bs, nb, epochs = 16, 2, 2
    cap = bs * nb

    # each client's first cap rows by index, padded slots on the padding
    # row; every epoch scans the shard in storage order
    x, y = fl_data.resident_train()
    rows = np.full((k_clients, cap), fl_data.pad_row, np.int32)
    for c in range(k_clients):
        idx = fl_data.client_indices[c][:cap]
        rows[c, :len(idx)] = idx
    rows = jnp.asarray(np.repeat(rows[:, None], epochs, axis=1))

    par = make_parallel_local_train(mlp_task, batch_size=bs, n_batches=nb,
                                    epochs=epochs)
    stacked_params, ep_losses = jax.jit(par)(global_params, x, y, rows,
                                             jnp.asarray(0.1))
    assert ep_losses.shape == (k_clients, epochs)    # [:, 0] is the probe loss
    assert np.isfinite(np.asarray(ep_losses)).all()
    # per-client params differ from the global and from each other
    w1 = np.asarray(stacked_params["w1"])
    assert w1.shape[0] == k_clients
    assert not np.allclose(w1[0], w1[1])
    # loss decreased for each client vs the global params
    for c in range(k_clients):
        p_c = jax.tree.map(lambda a: a[c], stacked_params)
        r = rows[c, 0]
        batch = {"x": x[r], "y": y[r],
                 "mask": (r != fl_data.pad_row).astype(jnp.float32)}
        l_after = float(mlp_task.loss(p_c, batch))
        l_before = float(mlp_task.loss(global_params, batch))
        assert l_after < l_before


def test_parallel_local_train_sharded_over_mesh(mlp_task, fl_data):
    """Same step under an explicit 1-device mesh with clients on 'data' —
    the pod-scale configuration (sharding is a no-op at 1 device but the
    pjit path is exercised)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    key = jax.random.PRNGKey(1)
    global_params = mlp_task.init(key)
    k_clients, bs, nb = 2, 8, 2
    cap = bs * nb
    rng = np.random.default_rng(0)
    n_rows = 3 * cap
    # the resident set: rows plus the all-zero padding row last
    x = jnp.asarray(np.concatenate([rng.normal(size=(n_rows, 32)),
                                    np.zeros((1, 32))]), jnp.float32)
    y = jnp.asarray(np.append(rng.integers(0, 10, size=n_rows), 0),
                    jnp.int32)
    rows = jnp.asarray(rng.integers(0, n_rows + 1, size=(k_clients, 1, cap)),
                       jnp.int32)

    par = make_parallel_local_train(mlp_task, batch_size=bs, n_batches=nb,
                                    epochs=1)
    shard = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    with mesh:
        f = jax.jit(par, in_shardings=(None, replicated, replicated, shard,
                                       None))
        stacked, losses = f(global_params, x, y, rows, jnp.asarray(0.1))
    assert losses.shape == (k_clients, 1)
    assert np.isfinite(np.asarray(losses)).all()


# ---------------------------------------------------------------------------
# executor parity: the vmapped pod-scale path must reproduce the sequential
# reference executor — at the stage level and across whole server rounds
# ---------------------------------------------------------------------------


def _tree_allclose(a, b, atol=1e-5, rtol=1e-4):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol, rtol=rtol)


def test_executors_stage_parity(mlp_task, fl_data):
    """Same requests through both executors -> same params and epoch losses,
    including heterogeneous client sizes (different padding buckets)."""
    from repro.data import FederatedData
    from repro.fl.engine import SequentialExecutor, VmappedExecutor, build_requests

    key = jax.random.PRNGKey(0)
    global_params = mlp_task.init(key)
    sizes = (40, 25, 120, 64, 9)
    data = FederatedData(fl_data.train, fl_data.test,
                         [fl_data.client_indices[c][:n]
                          for c, n in enumerate(sizes)])
    reqs = build_requests(range(len(sizes)), data, 3, seed=100, round_idx=0,
                          stride=0)
    kw = dict(lr=0.1, batch_size=32, prox_mu=0.0)
    seq = SequentialExecutor().run(mlp_task, global_params, reqs, **kw)
    par = VmappedExecutor().run(mlp_task, global_params, reqs, **kw)
    assert set(seq.params) == set(par.params)
    for c in seq.params:
        np.testing.assert_allclose(seq.losses[c], par.losses[c],
                                   atol=1e-5, rtol=1e-4)
        _tree_allclose(seq.params[c], par.params[c])


@pytest.mark.parametrize("start", ["shared", "stacked"])
def test_vmapped_mixed_shards_match_sequential(mlp_task, start):
    """Shards of 5, 7, 600, 1,000 and 1,100 rows (buckets of 8, 1,024 and
    2,048), two epochs and a proximal term: the vmapped step's gathers from
    the resident rows give the sequential executor's params and losses,
    from the shared global start or from per-client starts."""
    from repro.data import FederatedData, make_classification_data
    from repro.fl.engine import SequentialExecutor, VmappedExecutor, build_requests

    train, test = make_classification_data(n_samples=3000, seed=5)
    sizes = (5, 7, 600, 1000, 1100)
    shards = np.split(np.random.default_rng(5).permutation(sum(sizes)),
                      np.cumsum(sizes)[:-1])
    data = FederatedData(train, test, shards)
    gp = mlp_task.init(jax.random.PRNGKey(5))
    init = None
    if start == "stacked":
        # one client of each two-client bucket resumes from its own params
        init = {c: jax.tree.map(lambda a, c=c: a + 0.01 * (c + 1), gp)
                for c in (0, 2)}
    reqs = build_requests(range(len(sizes)), data, 2, seed=11, round_idx=1,
                          stride=1000, init_params=init)
    kw = dict(lr=0.1, batch_size=32, prox_mu=0.05)
    seq = SequentialExecutor().run(mlp_task, gp, reqs, **kw)
    par = VmappedExecutor().run(mlp_task, gp, reqs, **kw)
    assert set(seq.params) == set(par.params) == set(range(len(sizes)))
    for c in seq.params:
        np.testing.assert_allclose(seq.losses[c], par.losses[c],
                                   atol=1e-5, rtol=1e-4)
        _tree_allclose(seq.params[c], par.params[c])


@pytest.mark.parametrize("policy_name", ["fedavg", "fedmarl"])
def test_executor_parity_over_rounds(mlp_task, fl_data, policy_name):
    """3 full server rounds (probing and non-probing plans) give numerically
    matching global params under either executor."""
    from repro.fl import FLConfig, FLServer, build_policy

    hists, finals = [], []
    for executor in ("sequential", "vmapped"):
        cfg = FLConfig(n_devices=20, k_select=4, rounds=3, l_ep=2, lr=0.1,
                       seed=0, executor=executor)
        srv = FLServer(cfg, mlp_task, fl_data)
        hists.append(srv.run(build_policy(policy_name)))
        finals.append(srv.global_params)
    _tree_allclose(finals[0], finals[1])
    for ra, rb in zip(*hists):
        assert np.array_equal(ra.selected, rb.selected)
        assert ra.r_t == pytest.approx(rb.r_t)
        assert ra.acc == pytest.approx(rb.acc, abs=1e-6)
