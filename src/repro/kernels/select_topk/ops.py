"""Public op: fleet-scale top-K cohort selection with kernel/oracle dispatch.

``select_topk(scores_fn, states, mask, k)`` is THE selection path — every
policy that cuts a cohort by ranking candidates routes through it instead
of materializing a score vector and host-full-sorting it (the six
``np.argsort`` sites this op replaced).  Three scoring modes, one
deterministic contract:

* ``scores_fn`` is a Q-net params dict (w1/b1/w2/b2/w3/b3) — the FUSED
  path: ``impl="pallas"`` streams candidate tiles through the MLP head
  inside the Pallas kernel, carrying only the running top-K
  (:mod:`repro.kernels.select_topk.kernel`); ``impl="xla"`` scores then
  ``lax.top_k``s (the oracle); ``impl="auto"`` picks the compiled kernel
  on TPU and the oracle elsewhere.  The full score vector is never pulled
  to host either way.
* ``scores_fn`` is a callable — analytical utilities (Oort, FedMarl, the
  IL experts): scored in one vectorized call, then partial-selected on
  host in O(N + k log k) (``np.partition``, not a full sort).
* ``scores_fn`` is None — ``states`` already ARE the scores.

Contract (pinned by tests/test_select_topk.py): candidates ranked by score
descending, exact ties broken toward the LOWEST index on every path and
platform (host stable-select, XLA stable ``top_k``, kernel index-min
merge); masked candidates are excluded; exactly ``min(k, n_valid)``
winners come back.

``masked_topk`` is the jit-traceable sibling for in-graph call sites (the
DQN double-Q bootstrap) that need the same masking + tie rule inside a
compiled training step.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.select_topk.kernel import select_topk_pallas
from repro.kernels.select_topk.ref import NEG_INF, select_topk_ref
from repro.obs.profiling import (
    count_d2h,
    count_h2d,
    count_implicit_h2d,
    timed_call,
)


def resolve_select_impl(impl: str = "auto") -> str:
    """Map "auto" to the backend-appropriate implementation.

    The ``REPRO_SELECT_IMPL`` env var (``pallas`` | ``xla``) overrides the
    *auto* choice only — it lets CI and the kernel-vs-host golden test
    exercise the interpret-mode kernel path without code changes, while
    explicit per-call requests always get what they asked for.
    """
    if impl == "auto":
        impl = os.environ.get("REPRO_SELECT_IMPL", "auto")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown select-topk impl {impl!r}")
    return impl


def masked_topk(scores: jnp.ndarray, mask: jnp.ndarray, k: int):
    """Jit-traceable masked top-k: (values (k,), indices (k,)) by score
    descending, masked entries sunk to ``NEG_INF``, ties and exhausted
    slots resolving toward the lowest index (``lax.top_k`` is stable)."""
    return jax.lax.top_k(jnp.where(mask > 0, scores, NEG_INF), k)


def topk_indices(scores: np.ndarray, k: int,
                 mask: Optional[np.ndarray] = None) -> np.ndarray:
    """Host partial-select: indices of the k largest scores, descending,
    lowest-index tie-breaking — equal to ``np.argsort(-s, kind="stable")
    [:k]`` without the O(N log N) full sort (O(N) ``np.partition`` plus an
    O(k log k) ordering of the winners)."""
    s = np.asarray(scores)
    if mask is not None:
        s = np.where(np.asarray(mask) > 0, s, -np.inf)
    n = s.shape[0]
    k = min(int(k), n)
    if k <= 0:
        return np.empty(0, np.int64)
    if k >= n:
        return np.argsort(-s, kind="stable").astype(np.int64)
    kth = np.partition(s, n - k)[n - k]          # k-th largest value
    above = np.flatnonzero(s > kth)              # strictly better: < k of them
    ties = np.flatnonzero(s == kth)              # ascending index already
    idx = np.concatenate([above, ties[: k - len(above)]])
    order = np.argsort(-s[idx], kind="stable")   # small: k entries
    return idx[order].astype(np.int64)


def select_topk(scores_fn: Union[dict, Callable[[np.ndarray], np.ndarray], None],
                states: np.ndarray,
                mask: Optional[np.ndarray],
                k: int,
                *,
                bias: Optional[np.ndarray] = None,
                impl: str = "auto") -> Tuple[np.ndarray, np.ndarray]:
    """Select the top-``min(k, n_valid)`` candidates.

    Returns ``(indices, scores)``: int64 candidate indices by score
    descending (lowest-index ties) and their scores, masked candidates
    excluded.  ``mask`` is an (N,) boolean/0-1 validity mask (None = all
    valid); ``bias`` an optional (N,) additive score adjustment applied
    after scoring (fairness decay etc.).  See the module docstring for the
    three ``scores_fn`` modes.
    """
    states = np.asarray(states)
    n = states.shape[0]
    m = (np.ones(n, bool) if mask is None
         else np.asarray(mask).astype(bool))
    n_valid = int(m.sum())
    k_eff = min(int(k), n_valid)
    if k_eff <= 0:
        return np.empty(0, np.int64), np.empty(0, np.float32)

    if isinstance(scores_fn, dict):              # fused Q-net path
        b = (np.zeros(n, np.float32) if bias is None
             else np.asarray(bias, np.float32))
        feats = jnp.asarray(states, jnp.float32)
        mj = jnp.asarray(m, jnp.float32)
        bj = jnp.asarray(b)
        # transfer counters: no-ops unless a profiler is active
        count_h2d("select", feats, mj, bj)
        count_implicit_h2d("select", scores_fn, feats)
        # timed_call is a passthrough unless a profiler is active
        # (repro.obs.profiling): then the call is a span of its op name,
        # block_until_ready-fenced, and its wall-clock lands in the run
        # record's op table
        if resolve_select_impl(impl) == "pallas":
            vals, idx = timed_call("select_topk.pallas", select_topk_pallas,
                                   scores_fn, feats, mj, bj, k=min(int(k), n))
        else:
            vals, idx = timed_call("select_topk.xla", select_topk_ref,
                                   scores_fn, feats, mj, bj, k=min(int(k), n))
        idx, vals = idx[:k_eff], vals[:k_eff]
        count_d2h("select", idx, vals)
        return np.asarray(idx, np.int64), np.asarray(vals, np.float32)

    def _host_select():
        scores = states if scores_fn is None else np.asarray(scores_fn(states))
        scores = np.asarray(scores, np.float64)
        if bias is not None:
            scores = scores + np.asarray(bias, np.float64)
        idx = topk_indices(scores, k_eff, m)
        return idx, scores[idx]

    return timed_call("select_topk.host", _host_select)
