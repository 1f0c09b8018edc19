"""Server-side aggregation rules.

Synchronous rounds use :func:`fedavg` (data-size-weighted parameter
average).  The asynchronous engine aggregates a *buffer* of updates that
started from different global-model versions, so each update is additionally
scaled by a staleness weight of its version lag
(:func:`staleness_weight`, FedBuff/FedAsync-style) before being merged by
:func:`buffered_aggregate`.

Byzantine-robust reducers defend the merge against the attacks in
:mod:`repro.fl.attacks`: :func:`trimmed_mean` (coordinate-wise trimmed
weighted mean), :func:`coordinate_median`, and :func:`krum` /
:func:`multi_krum` (distance-score selection).  All are selectable through
``FLConfig.aggregator`` and dispatched via :func:`robust_aggregate`;
``"mean"`` reduces bit-for-bit to :func:`fedavg`, which is the anchor the
parity tests pin.  In the async path the robust reduce composes with
staleness: the buffer is robustly reduced first, then blended with the
current global model by the total staleness-shrunk mass (see
:func:`buffered_aggregate`).
"""
from __future__ import annotations

from typing import Any, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.profiling import count_d2h, count_implicit_h2d

Params = Any

STALENESS_KINDS = ("constant", "polynomial", "hinge")

AGGREGATORS = ("mean", "trimmed_mean", "coordinate_median", "krum",
               "multi_krum")


def fedavg(client_params: Sequence[Params], weights: Sequence[float]) -> Params:
    """Data-size-weighted parameter average (McMahan et al., 2017)."""
    # host (NumPy) updates merge on the host unless a device leaf is among
    # them; the counter is a no-op unless a profiler is active
    count_implicit_h2d("merge", *client_params)
    w = np.asarray(weights, np.float64)
    w = w / w.sum()

    def combine(*leaves):
        acc = leaves[0].astype(jnp.float32) * w[0]
        for wi, leaf in zip(w[1:], leaves[1:]):
            acc = acc + leaf.astype(jnp.float32) * wi
        return acc.astype(leaves[0].dtype)

    return jax.tree.map(combine, *client_params)


def _stack_leaves(client_params: Sequence[Params]) -> Params:
    """Stack the clients' pytrees leaf-wise to (m, ...) float32 arrays."""
    return jax.tree.map(
        lambda *ls: jnp.stack([l.astype(jnp.float32) for l in ls], axis=0),
        *client_params)


def trimmed_mean(client_params: Sequence[Params], weights: Sequence[float],
                 trim: int = 1) -> Params:
    """Coordinate-wise trimmed weighted mean (Yin et al., 2018).

    Per coordinate, the ``trim`` largest and ``trim`` smallest client values
    are discarded and the survivors averaged with their (renormalized) data
    weights.  With ``trim`` at least the adversary count every poisoned
    value is an extreme in the coordinates it perturbs, so the output is
    bounded by the honest min/max coordinate-wise — the property test's
    invariant.  ``trim=0`` returns :func:`fedavg` *bit-for-bit* (same code
    path), the reduction anchor.
    """
    m = len(client_params)
    if trim == 0:
        return fedavg(client_params, weights)
    if trim < 0 or 2 * trim >= m:
        raise ValueError(f"trimmed_mean needs 0 <= 2*trim < n updates; "
                         f"got trim={trim} with {m} updates")
    w = np.asarray(weights, np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)

    def combine(*leaves):
        stack = jnp.stack([l.astype(jnp.float32) for l in leaves], axis=0)
        count_implicit_h2d("merge", stack, *leaves)
        # per-coordinate rank of each client via double argsort (stable)
        ranks = jnp.argsort(jnp.argsort(stack, axis=0), axis=0)
        keep = (ranks >= trim) & (ranks < m - trim)
        wb = w.reshape((m,) + (1,) * (stack.ndim - 1))
        kept_w = jnp.where(keep, wb, 0.0)
        out = (kept_w * stack).sum(axis=0) / kept_w.sum(axis=0)
        return out.astype(leaves[0].dtype)

    return jax.tree.map(combine, *client_params)


def coordinate_median(client_params: Sequence[Params]) -> Params:
    """Coordinate-wise (unweighted) median of the client updates.

    The classic order-statistic defense: permutation-invariant in the
    update order, a fixed point on identical updates, and with a strict
    honest majority every output coordinate lies inside the honest range.
    Data weights are deliberately ignored — a weighted median would let an
    adversary claiming a huge dataset drag the order statistic, which is
    the attack surface this reducer exists to close.
    """

    def combine(*leaves):
        stack = jnp.stack([l.astype(jnp.float32) for l in leaves], axis=0)
        count_implicit_h2d("merge", stack, *leaves)
        return jnp.median(stack, axis=0).astype(leaves[0].dtype)

    return jax.tree.map(combine, *client_params)


def krum_scores(client_params: Sequence[Params], f: int = 1) -> np.ndarray:
    """(m,) Krum scores: for each update, the summed squared distance to its
    ``m - f - 2`` nearest peers (Blanchard et al., 2017).  Low score means
    the update sits in a dense honest cluster; outliers score high because
    their nearest peers are still far away.  Distances accumulate in
    float64 on host so scores are deterministic across backends."""
    m = len(client_params)
    count_d2h("merge", *client_params)
    flat = np.stack([
        np.concatenate([np.asarray(l, np.float64).ravel()
                        for l in jax.tree.leaves(p)])
        for p in client_params])
    sq = ((flat[:, None, :] - flat[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(sq, np.inf)
    # closest m - f - 2 peers (>= 1 even for tiny buffers)
    n_near = max(m - f - 2, 1)
    near = np.sort(sq, axis=1)[:, :n_near]
    return near.sum(axis=1)


def krum(client_params: Sequence[Params], f: int = 1) -> Params:
    """Select the single update with the lowest Krum score (lowest index on
    ties).  Guarantees the outlier is never chosen when ``m >= 2f + 3``."""
    idx = int(np.argmin(krum_scores(client_params, f=f)))
    return client_params[idx]


def multi_krum(client_params: Sequence[Params], weights: Sequence[float],
               f: int = 1, m_select: int | None = None) -> Params:
    """Multi-Krum: keep the ``m_select`` lowest-scoring updates (default
    ``m - f``) and :func:`fedavg` them with their data weights — Krum's
    outlier rejection with the mean's variance reduction."""
    m = len(client_params)
    if m_select is None:
        m_select = max(m - f, 1)
    m_select = int(np.clip(m_select, 1, m))
    scores = krum_scores(client_params, f=f)
    keep = np.argsort(scores, kind="stable")[:m_select]
    w = np.asarray(weights, np.float64)
    return fedavg([client_params[i] for i in keep], w[keep])


def robust_aggregate(client_params: Sequence[Params],
                     weights: Sequence[float], kind: str = "mean",
                     trim: int = 1, f: int = 1,
                     m_select: int | None = None) -> Params:
    """Dispatch an aggregation ``kind`` from :data:`AGGREGATORS`.

    ``"mean"`` is exactly :func:`fedavg` (bit-for-bit — the default path is
    untouched); the robust kinds take their knobs from ``trim`` / ``f`` /
    ``m_select``.  Krum's ``f`` is clamped to the buffer size (``m >= 2f+3``)
    so small early-round cohorts degrade gracefully instead of raising.
    """
    m = len(client_params)
    if kind == "mean":
        return fedavg(client_params, weights)
    if kind == "trimmed_mean":
        return trimmed_mean(client_params, weights,
                            trim=int(np.clip(trim, 0, max((m - 1) // 2, 0))))
    if kind == "coordinate_median":
        return coordinate_median(client_params)
    f_eff = int(np.clip(f, 0, max((m - 3) // 2, 0)))
    if kind == "krum":
        return krum(client_params, f=f_eff)
    if kind == "multi_krum":
        return multi_krum(client_params, weights, f=f_eff, m_select=m_select)
    raise ValueError(f"unknown aggregator {kind!r}; "
                     f"expected one of {AGGREGATORS}")


def staleness_weight(lag, kind: str = "constant", a: float = 0.5,
                     b: int = 4) -> np.ndarray:
    """s(lag) in (0, 1]: how much an update dispatched ``lag`` global-model
    versions ago still counts.

    * ``constant``   — s = 1 (staleness ignored; FedBuff's unweighted mean)
    * ``polynomial`` — s = (1 + lag)^-a  (FedAsync's polynomial decay)
    * ``hinge``      — s = 1 while lag <= b, then 1 / (1 + a * (lag - b))
                       (FedAsync's hinge: tolerate small lags, decay beyond)
    """
    lag = np.asarray(lag, dtype=np.float64)
    if kind == "constant":
        return np.ones_like(lag)
    if kind == "polynomial":
        return (1.0 + lag) ** (-a)
    if kind == "hinge":
        return np.where(lag <= b, 1.0, 1.0 / (1.0 + a * np.maximum(lag - b, 0.0)))
    raise ValueError(f"unknown staleness kind {kind!r}; "
                     f"expected one of {STALENESS_KINDS}")


def compose_staleness(lags_by_tier: Sequence, kind: str = "constant",
                      a: float = 0.5, b: int = 4) -> np.ndarray:
    """Effective staleness weight of an update that crossed several
    aggregation tiers: the product of each tier's :func:`staleness_weight`.

    In a hierarchical topology (:mod:`repro.fl.topology`) an update is
    first merged at its region edge with a *region* lag (versions behind
    the edge at dispatch), and the region delta is later merged at the root
    with a *root* lag (global versions behind at the region merge).  Each
    merge applies ``s(lag)`` independently, so the client's effective
    coefficient carries ``s(region_lag) * s(root_lag)`` — exactly what this
    returns given ``[region_lags, root_lags]`` (arrays broadcast).  With a
    single tier it reduces to :func:`staleness_weight`; at lag 0 every
    factor is exactly 1, which is what makes the flat single-region
    topology bit-for-bit identical to the plain engines.
    """
    out = None
    for lags in lags_by_tier:
        s = staleness_weight(np.asarray(lags), kind=kind, a=a, b=b)
        out = s if out is None else out * s
    if out is None:
        raise ValueError("compose_staleness needs at least one tier of lags")
    return out


def buffered_aggregate(global_params: Params,
                       client_params: Sequence[Params],
                       data_weights: Sequence[float],
                       lags: Sequence[int],
                       kind: str = "constant", a: float = 0.5,
                       b: int = 4, robust: str = "mean", trim: int = 1,
                       f: int = 1, m_select: int | None = None) -> Params:
    """Staleness-weighted merge of a buffer of async updates.

    Each update i carries coefficient ``c_i = w_i * s(lag_i)`` where ``w_i``
    is its normalized data weight and ``s`` the staleness weight; the new
    global model is ``(1 - sum(c)) * global + sum(c_i * p_i)`` — i.e. the
    mass a stale update loses stays with the current global model (a very
    stale buffer barely moves it).  With ``kind="constant"`` every ``s_i``
    is 1, the global term vanishes, and the merge reduces *exactly* to
    :func:`fedavg` of the buffer — the sync/async parity anchor.

    A non-``"mean"`` ``robust`` kind swaps the inner weighted sum for
    :func:`robust_aggregate` while keeping the staleness geometry: the
    buffer is robustly reduced with staleness-scaled weights
    ``w_i * s(lag_i)``, then blended with the current global model by the
    total retained mass ``shrink = sum(w_norm_i * s_i)`` —
    ``(1 - shrink) * global + shrink * reduce(buffer)``.  At ``robust=
    "mean"`` this factorization is algebraically the coefficient form
    above, and the code keeps the original path untouched so the default
    stays bit-for-bit.
    """
    s = staleness_weight(np.asarray(lags), kind=kind, a=a, b=b)
    w = np.asarray(data_weights, np.float64)
    if robust != "mean":
        if kind == "constant":
            return robust_aggregate(client_params, data_weights, kind=robust,
                                    trim=trim, f=f, m_select=m_select)
        shrink = float(((w / w.sum()) * s).sum())
        reduced = robust_aggregate(client_params, w * s, kind=robust,
                                   trim=trim, f=f, m_select=m_select)
        count_implicit_h2d("merge", global_params, reduced)
        return jax.tree.map(
            lambda g, r: (g.astype(jnp.float32) * (1.0 - shrink)
                          + r.astype(jnp.float32) * shrink).astype(g.dtype),
            global_params, reduced)
    coef = (w / w.sum()) * s
    if kind == "constant":
        return fedavg(client_params, data_weights)
    # host client leaves cross when they meet a device global model
    count_implicit_h2d("merge", global_params, *client_params)

    def combine(g, *leaves):
        acc = g.astype(jnp.float32) * (1.0 - coef.sum())
        for ci, leaf in zip(coef, leaves):
            acc = acc + leaf.astype(jnp.float32) * ci
        return acc.astype(g.dtype)

    return jax.tree.map(combine, global_params, *client_params)


def weighted_delta_aggregate(global_params: Params,
                             client_params: Sequence[Params],
                             weights: Sequence[float],
                             server_lr: float = 1.0) -> Params:
    """FedOpt-style: apply the weighted mean of client deltas with a server
    step size (reduces to fedavg at server_lr=1)."""
    avg = fedavg(client_params, weights)
    return jax.tree.map(
        lambda g, a: (g.astype(jnp.float32)
                      + server_lr * (a.astype(jnp.float32) - g.astype(jnp.float32))
                      ).astype(g.dtype),
        global_params, avg)
