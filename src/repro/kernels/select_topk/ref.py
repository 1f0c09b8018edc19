"""Pure-jnp oracle for fused Q-net scoring + top-K cohort selection.

The oracle is the "score-then-sort" inference path the fused kernel is
benchmarked against: apply the 3-layer Q-net MLP to every candidate's
feature row (materializing the full ``(N,)`` score vector), then cut the
cohort with :func:`jax.lax.top_k`.

Semantics shared with the Pallas kernel (the contract the parity tests pin):

* masked candidates (``mask == 0``) score ``NEG_INF`` and are only selected
  once every valid candidate is exhausted (``k > n_valid``);
* ties break deterministically toward the LOWEST candidate index —
  ``lax.top_k`` is stable, so equal scores come out in ascending index
  order on every backend;
* ``bias`` is a per-candidate additive score adjustment applied after the
  MLP (selection-side terms that are not part of the learned net, e.g.
  FedRank's over-participation fairness decay).

The MLP mirrors :func:`repro.core.qnet.apply_qnet` operation for operation
(same params dict: w1/b1/w2/b2/w3/b3) but is re-implemented here so the
kernel package stays below ``repro.core`` in the layering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Large negative fp32 sentinel for masked candidates.  NOT -inf: arithmetic
# on the sentinel stays finite, and fp32 all the way keeps the kernel and
# oracle bit-identical on the masked tail.
NEG_INF = -3.0e38

# Full-f32 MXU passes for the scoring matmuls in the oracle AND the kernel:
# at DEFAULT precision XLA rounds f32 operands to one bf16 pass on TPU, and
# a 1e-3 score error reorders near-tied candidates between the two paths.
# Off-TPU f32 dots are exact either way.
HIGHEST = jax.lax.Precision.HIGHEST


def qnet_scores_ref(params, feats: jnp.ndarray) -> jnp.ndarray:
    """feats (N, F) -> scores (N,): the Q-net 3-layer MLP head, identical
    math to ``repro.core.qnet.apply_qnet``."""
    f = feats.astype(jnp.float32)
    dot = functools.partial(jnp.dot, precision=HIGHEST)
    h = jax.nn.relu(dot(f, params["w1"]) + params["b1"])
    h = jax.nn.relu(dot(h, params["w2"]) + params["b2"])
    return (dot(h, params["w3"]) + params["b3"])[..., 0]


@functools.partial(jax.jit, static_argnames=("k",))
def select_topk_ref(params, feats: jnp.ndarray, mask: jnp.ndarray,
                    bias: jnp.ndarray, *, k: int):
    """XLA oracle: full score vector + ``lax.top_k``.

    feats (N, F), mask (N,), bias (N,) -> (values (k,), indices (k,)),
    descending score with lowest-index tie-breaking; k must be <= N.
    """
    n = feats.shape[0]
    # pad the scoring matmul to the kernel's sublane multiple: XLA lowers
    # M=1 to a differently-accumulated gemv, so without this the oracle is
    # 1 ulp off the fused kernel on single-candidate inputs
    n8 = max(8, -(-n // 8) * 8)
    f = jnp.pad(feats, ((0, n8 - n), (0, 0))) if n8 != n else feats
    s = qnet_scores_ref(params, f)[:n] + bias.astype(jnp.float32)
    s = jnp.where(mask > 0, s, NEG_INF)
    return jax.lax.top_k(s, k)
