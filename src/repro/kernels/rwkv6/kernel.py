"""Pallas TPU kernel: chunkwise-parallel RWKV6 (Finch) WKV recurrence.

TPU adaptation of the CUDA wkv6 kernel: instead of one thread per channel
running the token recurrence, the sequence is processed in L-token chunks —
intra-chunk contributions become dense (L, n) x (n, L) / (L, L) x (L, n)
matmuls on the MXU; the (n, n) recurrent state lives in VMEM scratch and is
carried across the sequential chunk grid dimension.  Decay products are kept
in log space; intra-chunk pair factors use chunk-local exponents
exp(cum_{t-1} - cum_s) built from two rank-1-stable factors, which is exact
in fp32 at L <= 64 for the decay ranges rwkv6 produces.

Grid: (BH, T / L).  Inputs r/k/v/logw: (BH, T, n); u: (BH, n); s0: (BH,n,n).
Outputs: y (BH, T, n), s_final (BH, n, n).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sf_ref,
            state_scr, *, chunk: int, n: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_scr[:] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # (L, n)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # (n,)

    cum = jnp.cumsum(lw, axis=0)              # inclusive
    total = cum[-1]                           # (n,)
    cum_prev = cum - lw                       # exclusive

    r_f = r * jnp.exp(cum_prev)               # (L, n)
    k_f = k * jnp.exp(-cum)

    # intra-chunk strictly-lower attention + diagonal bonus
    scores = jax.lax.dot_general(r_f, k_f, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(rows > cols, scores, 0.0)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u[None, :] * k, axis=1)          # (L,)
    y = y + diag[:, None] * v

    # cross-chunk: contribution of carried state, then state update
    S = state_scr[:]
    y = y + jax.lax.dot_general(r_f, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    k_state = k * jnp.exp(total[None, :] - cum)          # decayed to chunk end
    S_new = jnp.exp(total)[:, None] * S + jax.lax.dot_general(
        k_state, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_scr[:] = S_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _fin():
        sf_ref[0] = S_new.astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                logw: jnp.ndarray, u: jnp.ndarray, s0: jnp.ndarray,
                *, chunk: int = 64, interpret: bool = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``interpret=None`` resolves to interpret mode off-TPU and compiled
    mode on TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bh, t, n = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    grid = (bh, n_chunks)

    kernel = functools.partial(_kernel, chunk=chunk, n=n, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0))
    vec_spec = pl.BlockSpec((1, n), lambda b, j: (b, 0))
    mat_spec = pl.BlockSpec((1, n, n), lambda b, j: (b, 0, 0))

    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, vec_spec, mat_spec],
        out_specs=[seq_spec, mat_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, t, n), r.dtype),
                   jax.ShapeDtypeStruct((bh, n, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u, s0)
    return y, s_fin
