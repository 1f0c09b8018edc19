"""Pallas TPU kernel: fused Q-net scoring -> running top-K cohort selection.

FedRank's inference hot path at fleet scale: rank 100k-1M candidate probe
states and emit the top-K cohort.  The host path scores everything, copies
the full ``(N,)`` vector off-device and full-sorts it — O(N log N) compare
traffic plus a score vector round trip that dwarfs K for production fleets.

The TPU adaptation mirrors the chunked-recurrence structure of the rwkv6
kernel (long scan, small carried state): candidates stream through the
sequential tile grid, each ``(block, F)`` feature tile runs the 3-layer
Q-net MLP head on the MXU *inside the kernel*, and the only state carried
across tiles is the running top-K — a ``(K,)`` value/index pair living in
the revisited output block (legal on TPU: grid iterations are sequential,
exactly like the pairwise_rank accumulator).  The full score vector is
never materialized: scores exist one VMEM tile at a time and HBM traffic
is the feature stream plus O(K).

Merge step: the carried top-K is concatenated with the tile's scores and
the new top-K is extracted by K passes of (max, lowest-index-argmax,
knock-out) — exact selection with deterministic lowest-index tie-breaking,
implemented with pure max/min/where vector ops (no sort primitive, which
Mosaic does not lower).  Selected entries are knocked out by index, with
their index retired to INT32_MAX so exhausted/masked ties keep resolving
toward the lowest live index.

Grid: (N / block,).  feats (N, F); mask/bias (1, N) rows; Q-net params as
full-array blocks.  Outputs: values (1, K_pad) fp32, indices (1, K_pad)
int32, both revisited every step.  Padding rows carry mask 0 and indices
>= N; virgin top-K slots carry NEG_INF at indices >= N_pad so every real
candidate — even a masked one — outranks them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.select_topk.ref import HIGHEST, NEG_INF

DEFAULT_BLOCK = 512
_INT32_MAX = 2**31 - 1  # plain int: jnp constants can't be captured by kernels


def _kernel(f_ref, m_ref, b_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref,
            b3_ref, vals_ref, idx_ref, *, block: int, k_pad: int, n_pad: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        # virgin slots: NEG_INF at indices beyond every padded candidate,
        # ascending so the carried tie order stays ascending-by-index
        vals_ref[0, :] = jnp.full((k_pad,), NEG_INF, jnp.float32)
        idx_ref[0, :] = n_pad + jax.lax.broadcasted_iota(
            jnp.int32, (1, k_pad), 1)[0]

    # --- fused Q-net MLP head over this tile (MXU) ---------------------
    feats = f_ref[:].astype(jnp.float32)                       # (block, F)
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    h = jax.nn.relu(dot(feats, w1_ref[:]) + b1_ref[0, :][None, :])
    h = jax.nn.relu(dot(h, w2_ref[:]) + b2_ref[0, :][None, :])
    s = dot(h, w3_ref[:])[:, 0] + b3_ref[0, 0]                 # (block,)
    s = s + b_ref[0, :].astype(jnp.float32)
    s = jnp.where(m_ref[0, :] > 0, s, NEG_INF)
    gidx = t * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)[0]

    # --- merge tile scores into the carried top-K ----------------------
    work_v = jnp.concatenate([vals_ref[0, :], s])       # (k_pad + block,)
    work_i = jnp.concatenate([idx_ref[0, :], gidx])
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)[0]

    def extract(j, carry):
        wv, wi, ov, oi = carry
        vmax = jnp.max(wv)
        imin = jnp.min(jnp.where(wv == vmax, wi, _INT32_MAX))
        # masked write of slot j: Mosaic does not lower dynamic_update_slice
        ov = jnp.where(slot == j, vmax, ov)
        oi = jnp.where(slot == j, imin, oi)
        kill = wi == imin                       # indices are unique
        wv = jnp.where(kill, NEG_INF, wv)
        wi = jnp.where(kill, _INT32_MAX, wi)    # retire from tie-breaking
        return wv, wi, ov, oi

    _, _, new_v, new_i = jax.lax.fori_loop(
        0, k_pad, extract,
        (work_v, work_i,
         jnp.full((k_pad,), NEG_INF, jnp.float32),
         jnp.full((k_pad,), _INT32_MAX, jnp.int32)))
    vals_ref[0, :] = new_v
    idx_ref[0, :] = new_i


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def select_topk_pallas(params, feats: jnp.ndarray, mask: jnp.ndarray,
                       bias: jnp.ndarray, *, k: int,
                       block: int = DEFAULT_BLOCK, interpret: bool = None):
    """feats (N, F), mask (N,), bias (N,) -> (values (K_pad,), indices
    (K_pad,)) with K_pad = k rounded up to a multiple of 8; the first
    min(k, N) entries match :func:`select_topk_ref` exactly.

    ``interpret=None`` resolves to interpret mode off-TPU (the CPU/ref
    fallback) and compiled mode on TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, f = feats.shape
    k_pad = max(8, -(-int(k) // 8) * 8)
    block = min(block, max(8, -(-n // 8) * 8))
    n_pad = -(-n // block) * block
    pad = n_pad - n

    feats = feats.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    if pad:
        feats = jnp.pad(feats, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, (0, pad))            # padding rows masked out
        bias = jnp.pad(bias, (0, pad))
    mask = mask.reshape(1, n_pad)
    bias = bias.reshape(1, n_pad)

    h = params["w1"].shape[1]
    w1 = params["w1"].astype(jnp.float32)
    b1 = params["b1"].astype(jnp.float32).reshape(1, h)
    w2 = params["w2"].astype(jnp.float32)
    b2 = params["b2"].astype(jnp.float32).reshape(1, h)
    w3 = params["w3"].astype(jnp.float32).reshape(h, 1)
    b3 = params["b3"].astype(jnp.float32).reshape(1, 1)

    grid = (n_pad // block,)
    tile_spec = pl.BlockSpec((block, f), lambda t: (t, 0))
    row_spec = pl.BlockSpec((1, block), lambda t: (0, t))
    full = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0))
    out_spec = pl.BlockSpec((1, k_pad), lambda t: (0, 0))

    vals, idx = pl.pallas_call(
        functools.partial(_kernel, block=block, k_pad=k_pad, n_pad=n_pad),
        grid=grid,
        in_specs=[tile_spec, row_spec, row_spec,
                  full((f, h)), full((1, h)), full((h, h)), full((1, h)),
                  full((h, 1)), full((1, 1))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
                   jax.ShapeDtypeStruct((1, k_pad), jnp.int32)],
        interpret=interpret,
    )(feats, mask, bias, w1, b1, w2, b2, w3, b3)
    return vals[0], idx[0]
