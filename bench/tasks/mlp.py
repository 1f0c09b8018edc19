"""Task ``mlp``: the program's dense ReLU classifier (``repro.fl.MLPTask``),
``dim -> hidden -> hidden -> n_classes``, as the clients train it.

What the harness needs of a task, found by the name in a configuration's
``model.task``: the program's task object, the initial weights as a pure
function of a key, the FLOPs the work needs, and the plain reference of a
client's local training (masked mean softmax cross-entropy, SGD on each
batch of the feed in :func:`bench.reference.feed`), in float64 NumPy.
"""
from __future__ import annotations

import numpy as np

from bench import reference as R


def dims(model: dict):
    return (model["dim"], model["hidden"], model["hidden"], model["n_classes"])


def param_count(model: dict) -> int:
    d = dims(model)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def build(model: dict):
    """The program's task at the configuration's widths."""
    from repro.fl import MLPTask

    return MLPTask(model["dim"], model["hidden"], model["n_classes"])


def init_fn(model: dict):
    """``key -> params``: LeCun truncated normal weights, zero biases, in
    float32 (jit-able; the harness makes them on the device)."""
    import jax
    import jax.numpy as jnp

    d = dims(model)

    def init(key):
        keys = jax.random.split(key, len(d) - 1)
        p = {}
        for i, (k, a, b) in enumerate(zip(keys, d[:-1], d[1:]), start=1):
            p[f"w{i}"] = (jax.random.truncated_normal(k, -2.0, 2.0, (a, b))
                          / np.sqrt(a)).astype(jnp.float32)
            p[f"b{i}"] = jnp.zeros((b,), jnp.float32)
        return p

    return init


def train_flops(model: dict, sample_epochs: int) -> float:
    """Forward and backward: 6 x params per trained sample per epoch."""
    return 6.0 * param_count(model) * sample_epochs


def eval_flops(model: dict, rows: int) -> float:
    """One forward pass: 2 x params per evaluated row."""
    return 2.0 * param_count(model) * rows


def loss(p: dict, x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """(C,) mean softmax cross-entropy of each client's params ``p`` (leaves
    (C, ...)) over its rows ``x`` (C, n, d), ``y`` (C, n), in float64."""
    p = R.params_as(p, R.F64)
    h = np.asarray(x, np.float64)
    for i in (1, 2):
        h = np.maximum(np.matmul(h, p[f"w{i}"]) + p[f"b{i}"][:, None, :], 0)
    z = np.matmul(h, p["w3"]) + p["b3"][:, None, :]
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    gold = np.take_along_axis(logp, np.asarray(y)[..., None], -1)[..., 0]
    return -gold.mean(1)


def train(p0: dict, x: np.ndarray, y: np.ndarray, mask: np.ndarray,
          order: np.ndarray, lr: float, prec=R.F64) -> dict:
    """Local SGD for a stack of C clients: ``p0`` leaves (C, ...), ``x``
    (C, cap, d), ``y`` and ``mask`` (C, cap), ``order`` (C, epochs,
    n_batches, batch) row indices.  Returns the (C, ...) params."""
    p = R.params_as(p0, prec)
    c = len(y)
    n_cls = p["w3"].shape[-1]
    rows = np.arange(c)[:, None]
    for e in range(order.shape[1]):
        for b in range(order.shape[2]):
            idx = order[:, e, b]
            xb = R.rnd(x[rows, idx], prec)
            yb, mb = y[rows, idx], mask[rows, idx]
            a1 = R.rnd(R.f(R.mm(xb, p["w1"], prec), prec)
                       + R.f(p["b1"][:, None, :], prec), prec)
            h1 = np.maximum(a1, 0)
            a2 = R.rnd(R.f(R.mm(h1, p["w2"], prec), prec)
                       + R.f(p["b2"][:, None, :], prec), prec)
            h2 = np.maximum(a2, 0)
            z = R.f(R.mm(h2, p["w3"], prec), prec) + R.f(p["b3"][:, None, :],
                                                          prec)
            z = z - z.max(-1, keepdims=True)
            sm = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
            onehot = np.zeros_like(sm)
            onehot[rows, np.arange(yb.shape[1])[None, :], yb] = 1.0
            denom = np.maximum(mb.sum(1), 1.0)[:, None, None]
            dz = R.rnd((sm - onehot) * mb[:, :, None] / denom, prec)
            tr = lambda a: np.swapaxes(a, 1, 2)   # noqa: E731
            g = {"w3": R.mm(tr(h2), dz, prec), "b3": dz.sum(1)}
            da2 = R.rnd(R.f(R.mm(dz, tr(p["w3"]), prec), prec)
                        * (R.f(a2, prec) > 0), prec)
            g["w2"], g["b2"] = R.mm(tr(h1), da2, prec), da2.sum(1)
            da1 = R.rnd(R.f(R.mm(da2, tr(p["w2"]), prec), prec)
                        * (R.f(a1, prec) > 0), prec)
            g["w1"], g["b1"] = R.mm(tr(xb), da1, prec), da1.sum(1)
            p = {k: R.rnd(R.f(p[k], prec) - lr * R.f(g[k], prec), prec)
                 for k in p}
    return p
