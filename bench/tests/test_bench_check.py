"""The comparison that decides ``correct`` on parameter trees of any
nesting and size: leaves by path, faults planted on one nested leaf, and
the host memory that ``check.numbers`` adds for a large model."""
import tracemalloc
import types

import numpy as np
import pytest

LIMITS = {"step_gap": 0.2, "merge_err": 0.001}
CONFIG = {"client": {"lr": 0.1, "local_batch": 4}, "model": {"task": "t"}}
TRAFFIC = {"policy": "fedavg", "aggregator": "mean", "mode": "sync"}


def _train(p0, x, y, mask, order, lr, prec=None):
    """A cheap reference: each leaf shrinks by ``lr`` times the mean of the
    client's rows, in float32."""
    from bench.check import leaves, rebuild

    rate = np.float32(lr) * np.asarray(x, np.float32).mean(axis=(1, 2))
    flat = leaves(p0)

    def step(k):
        p = np.asarray(flat[k], np.float32)
        return p - rate.reshape((-1,) + (1,) * (p.ndim - 1)) * p

    return rebuild(p0, step)


def _loss(p, x, y):
    from bench.check import leaves

    return sum(np.square(np.asarray(v, np.float64)).reshape(len(v), -1)
               .mean(1) for v in leaves(p).values())


TASK = types.SimpleNamespace(train=_train, loss=_loss)


@pytest.fixture
def nested_task(monkeypatch):
    from bench import fleet

    real = fleet.module
    monkeypatch.setattr(fleet, "module", lambda kind, name: (
        TASK if kind == "tasks" else real(kind, name)))


def _model(width: int, layers: int, seed: int = 0):
    """A transformer-shaped tree: an embedding, a list of layers of nested
    dicts and tuples, a head."""
    rng = np.random.default_rng(seed)

    def w(*shape):
        return rng.standard_normal(shape, np.float32)

    return {"embed": w(2 * width, width),
            "layers": [{"attn": {"wq": w(width, width), "wo": w(width, width),
                                 "norm": w(width)},
                        "mlp": (w(width, 2 * width), w(2 * width, width))}
                       for _ in range(layers)],
            "head": w(width, 2 * width)}


def _record(g, fault=None, leaf="layers/1/mlp/0"):
    """One window aggregation of two clients, trained and merged soundly by
    the reference, or with ``fault`` planted on one nested leaf."""
    from bench.check import leaves, rebuild
    from bench.merges.mean import merge, merge_leaf

    reqs, outs = [], {}
    for cid in (3, 8):
        x = np.full((8, 4), 0.5 + 0.25 * cid, np.float32)
        reqs.append((cid, x, np.zeros(8, np.int64), 1, False, cid))
        stacked = leaves(_train(rebuild(g, lambda k: leaves(g)[k][None]),
                                x[None], None, None, None, 0.1))
        outs[cid] = {k: v[0] for k, v in stacked.items()}
    weights = np.array([8.0, 8.0])
    ups = list(outs.values())
    merged = merge(leaves(g), ups, weights, None, TRAFFIC)
    if fault == "client_left_out":
        merged[leaf] = merge_leaf(leaves(g)[leaf], [ups[0][leaf]],
                                  weights[:1], None, TRAFFIC)
    merged = {k: v.astype(np.float32) for k, v in merged.items()}
    for out in ups:
        if fault == "unchanged":
            out[leaf] = leaves(g)[leaf]
        elif fault == "doubled":
            out[leaf] = 2 * out[leaf] - leaves(g)[leaf]
    outs = {c: rebuild(g, o.get) for c, o in outs.items()}
    merged = rebuild(g, merged.get)
    return {"g_in": g, "train": [(g, reqs, outs)], "probe_cut": [],
            "select": [], "merge": (None, list(outs.values()), weights, None,
                                    merged),
            "g_out": merged, "n_merged": 2}


def _numbers(rec, index=5):
    from bench import check

    return check.numbers({index: rec}, [], rec["g_in"], CONFIG, TRAFFIC,
                         None)


def test_leaves_by_path():
    from bench.check import is_finite, leaves, rebuild

    flat = {"w1": np.ones(2), "b1": np.zeros(1)}
    assert list(leaves(flat)) == ["w1", "b1"]
    assert all(leaves(flat)[k] is v for k, v in flat.items())
    tree = {"layers": [{"wq": 1.0}, {"wq": 2.0}], "mlp": (3.0, 4.0)}
    assert leaves(tree) == {"layers/0/wq": 1.0, "layers/1/wq": 2.0,
                            "mlp/0": 3.0, "mlp/1": 4.0}
    assert rebuild(tree, leaves(tree).get) == tree
    assert is_finite(_model(4, 2))
    bad = _model(4, 2)
    bad["layers"][1]["mlp"][0][0, 0] = np.nan
    assert not is_finite(bad)


@pytest.mark.parametrize("fault,number", [(None, None),
                                          ("unchanged", "step_gap"),
                                          ("doubled", "step_gap"),
                                          ("client_left_out", "merge_err")])
def test_a_fault_on_one_nested_leaf(nested_task, fault, number):
    """Planted on one leaf of a nested tree, each fault fails its number as
    on a flat one; the sound record passes both, on the window's
    aggregation and on the first."""
    g = _model(16, 2)
    for index in (5, 0):
        out = _numbers(_record(g, fault), index)
        if fault is None:
            assert out["step_gap"] < 1e-6 and out["merge_err"] < 1e-5
            if index == 0:
                assert out["fit_gap"] < 1e-6
        else:
            assert out[number] > LIMITS[number]


def test_host_memory_of_a_large_nested_model(nested_task):
    """Two clients of a 16.8M-parameter nested model, one window
    aggregation: the check adds at most 2.5 times the model's float32
    bytes to the host's peak."""
    from bench.check import leaves

    g = _model(1024, 2)
    n_bytes = sum(v.nbytes for v in leaves(g).values())
    rec = _record(g)
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        out = _numbers(rec)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert out["step_gap"] < 1e-6 and out["merge_err"] < 1e-5
    assert peak <= 2.5 * n_bytes, (peak, n_bytes)
