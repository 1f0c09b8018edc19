"""Pallas TPU flash attention: blockwise online-softmax with GQA folding,
causal and sliding-window masking, and block-level mask skipping.

TPU adaptation (vs. the CUDA flash-attention schedule):
* grid = (B * KV_heads, n_q_blocks, n_kv_blocks) — TPU grid steps execute
  sequentially, so the (m, l, acc) running softmax state lives in VMEM
  scratch persisted across the innermost kv dimension; no atomics/warp
  shuffles needed.
* GQA is folded into the q-block rows: q is laid out (B*KV, S*G, Dh) with the
  G query heads of a kv group interleaved per position, so K/V tiles are
  loaded ONCE per group (the GQA bandwidth win) and the MXU sees
  (BQ*G, Dh) x (Dh, BK) matmuls.
* fully-masked (q_block, kv_block) tiles are skipped with pl.when — the
  causal schedule does ~half the work, the sliding-window schedule O(S*W).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, group: int,
            causal: bool, window: Optional[int], n_kv: int):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # query positions of this block's rows (rows are s*G+g interleaved)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    qpos = (i * block_q + rows) // group
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    kpos = j * block_k + cols

    q_first = (i * block_q) // group            # min q position in block
    q_last = (i * block_q + block_q - 1) // group

    def compute():
        q = q_ref[0].astype(jnp.float32)        # (BQ, Dh)
        k = k_ref[0].astype(jnp.float32)        # (BK, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    # block-level skipping: visit only blocks that can contain valid pairs
    live = True
    if causal:
        live = (j * block_k) <= q_last                      # not strictly future
    if window is not None:
        live = jnp.logical_and(live, (j + 1) * block_k - 1 > q_first - window)

    if causal or window is not None:
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group", "causal", "window", "block_q", "block_k",
                     "interpret"))
def flash_attention_folded(
    q: jnp.ndarray,     # (BKV, SG, Dh) — GQA-folded rows
    k: jnp.ndarray,     # (BKV, S, Dh)
    v: jnp.ndarray,     # (BKV, S, Dh)
    *,
    group: int,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``interpret=None`` resolves to interpret mode off-TPU and compiled
    mode on TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bkv, sg, dh = q.shape
    s = k.shape[1]
    block_q = min(block_q, sg)
    block_k = min(block_k, s)
    assert sg % block_q == 0 and s % block_k == 0
    grid = (bkv, sg // block_q, s // block_k)
    scale = dh ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, group=group,
        causal=causal, window=window, n_kv=s // block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bkv, sg, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
