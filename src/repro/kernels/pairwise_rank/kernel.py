"""Pallas TPU kernel: tiled pairwise RankNet loss.

At production FL scale the candidate pool is 10^4-10^5 devices per round, so
the O(N^2) pair reduction is the scheduler's compute hot spot.  The TPU
adaptation: (BN x BN) pair tiles streamed through VMEM with the row/column
score vectors each loaded once per tile row/column (HBM traffic O(N^2/BN)
instead of materializing the N^2 matrices), MXU-aligned BN=128 lanes, and a
scalar accumulator revisited across the sequential TPU grid.

Grid: (N/BN, N/BN); outputs (sum, count) accumulate in a (1,1) SMEM block
that every grid step revisits (legal on TPU: grid iterations are
sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK = 128


def _kernel(s_row_ref, s_col_ref, t_row_ref, t_col_ref, m_row_ref, m_col_ref,
            sum_ref, cnt_ref, *, block: int, hard: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        sum_ref[0, 0] = jnp.float32(0.0)
        cnt_ref[0, 0] = jnp.float32(0.0)

    s_i = s_row_ref[0, :].astype(jnp.float32)      # (BN,)
    s_j = s_col_ref[0, :].astype(jnp.float32)
    t_i = t_row_ref[0, :].astype(jnp.float32)
    t_j = t_col_ref[0, :].astype(jnp.float32)
    m_i = m_row_ref[0, :].astype(jnp.float32)
    m_j = m_col_ref[0, :].astype(jnp.float32)

    logits = s_i[:, None] - s_j[None, :]           # (BN, BN)
    t_diff = t_i[:, None] - t_j[None, :]
    if hard:
        # imitation targets: hard 0/1 orders from expert utilities (ties 0.5)
        tgt = jnp.where(t_diff > 0, 1.0, jnp.where(t_diff < 0, 0.0, 0.5))
    else:
        tgt = jax.nn.sigmoid(t_diff)
    pm = m_i[:, None] * m_j[None, :]
    # knock out the diagonal on diagonal tiles
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0) + i * block
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1) + j * block
    pm = jnp.where(row_ids == col_ids, 0.0, pm)

    bce = jnp.maximum(logits, 0.0) - logits * tgt + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    sum_ref[0, 0] += jnp.sum(bce * pm)
    cnt_ref[0, 0] += jnp.sum(pm)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "hard"))
def pairwise_rank_pallas(scores: jnp.ndarray, targets: jnp.ndarray,
                         mask: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
                         interpret: bool = None, hard: bool = False
                         ) -> jnp.ndarray:
    """scores/targets/mask: (N,) -> scalar mean pairwise BCE.

    N is padded to a multiple of ``block``; padded entries carry mask 0.
    ``hard=True`` uses hard 0/1 pair targets from the target score vector
    (ties 0.5) — the imitation-learning objective of ``pairwise_bce_hard``.
    ``interpret=None`` resolves to interpret mode off-TPU (the CPU/ref
    fallback) and compiled mode on TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = scores.shape[0]
    n_pad = ((n + block - 1) // block) * block
    pad = n_pad - n

    def prep(x, fill=0.0):
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, (0, pad), constant_values=fill)
        return x.reshape(1, n_pad)  # leading unit dim: TPU-friendly 2D layout

    s = prep(scores)
    t = prep(targets)
    m = prep(mask.astype(jnp.float32))
    grid = (n_pad // block, n_pad // block)

    row_spec = pl.BlockSpec((1, block), lambda i, j: (0, i))
    col_spec = pl.BlockSpec((1, block), lambda i, j: (0, j))
    # scalar accumulators live in SMEM: Mosaic cannot store scalars to VMEM
    out_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                            memory_space=pltpu.SMEM)

    out_sum, out_cnt = pl.pallas_call(
        functools.partial(_kernel, block=block, hard=hard),
        grid=grid,
        in_specs=[row_spec, col_spec, row_spec, col_spec, row_spec, col_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(s, s, t, t, m, m)
    return out_sum[0, 0] / jnp.maximum(out_cnt[0, 0], 1.0)
