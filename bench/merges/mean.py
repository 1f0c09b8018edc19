"""Aggregator ``mean``: the plain reference of the server's merge.

Sync rounds: the data-size-weighted mean of the client params (FedAvg).
Buffered async merges (FedBuff): ``(1 - sum c) g + sum c_i p_i`` with
``c_i = w_i / sum w * s(lag_i)``, ``s`` the traffic's staleness weighting.

The merge is elementwise, so :func:`merge_leaf` takes one leaf, or one
block of a leaf's elements, at a time (:mod:`bench.check` walks the tree);
:func:`merge` is the same over a whole ``{path: leaf}`` tree.
"""
from __future__ import annotations

import numpy as np

from bench import reference as R


def _staleness(traffic: dict, lags):
    lags = np.asarray(lags, np.float64)
    if traffic["staleness"] == "polynomial":
        return (1.0 + lags) ** (-traffic["staleness_a"])
    raise KeyError(f"no reference for staleness {traffic['staleness']!r}")


def merge_leaf(g_leaf, client_leaves: list, weights, lags, traffic: dict,
               prec=R.F64) -> np.ndarray:
    """The merged leaf from the global model's ``g_leaf`` and each client's
    leaf of the same shape (NumPy or device arrays, any float dtype)."""
    w = np.asarray(weights, np.float64)
    coef = w / w.sum()
    buffered = traffic["mode"] == "async"
    if buffered:
        coef = coef * _staleness(traffic, lags)
    stack = np.stack([R.f(R.rnd(c, prec), prec) for c in client_leaves])
    acc = np.tensordot(R.f(coef, prec), stack, axes=1)
    if buffered:
        acc = acc + R.f(R.rnd(g_leaf, prec), prec) * (1.0 - coef.sum())
    return R.rnd(acc, prec)


def merge(g_in: dict, clients: list, weights, lags, traffic: dict,
          prec=R.F64) -> dict:
    return {k: merge_leaf(g_in[k], [c[k] for c in clients], weights, lags,
                          traffic, prec) for k in g_in}
