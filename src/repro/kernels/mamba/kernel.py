"""Pallas TPU kernel: Mamba-1 selective scan with VMEM-resident state.

§Perf pair A showed the XLA per-token scan is memory-bound: every token step
round-trips the (B, inner, state) recurrent state and the output stack
through HBM (roofline memory term 992 s on hymba train_4k; loop unrolling
recovers only ~2x).  Mamba-1's (channel x state) data-dependent decay is not
matmul-separable (unlike rwkv6/mamba-2), so the chunked-parallel trick does
not apply — the TPU-native answer is to keep the recurrence but make the
state VMEM-RESIDENT: each grid step loads an L-token chunk of inputs once,
runs the recurrence entirely in VMEM scratch (fori_loop), and writes the
L-token output chunk once.  HBM traffic drops from O(T * inner * state) to
O(T * (inner + state)) — the input/output floor.

Grid: (B, inner_blocks, T / L); the (iblk, state) state scratch persists
across the sequential chunk dimension.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hf_ref,
            h_scr, *, chunk: int, n_chunks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[:] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # (L, iblk)
    dt = dt_ref[0].astype(jnp.float32)        # (L, iblk)
    bm = b_ref[0].astype(jnp.float32)         # (L, state)
    cm = c_ref[0].astype(jnp.float32)         # (L, state)
    a = a_ref[0].astype(jnp.float32)          # (iblk, state)

    def body(t, carry):
        h, y = carry
        da = jnp.exp(dt[t][:, None] * a)                      # (iblk, state)
        h = da * h + (dt[t] * x[t])[:, None] * bm[t][None, :]
        y = y.at[t].set(h @ cm[t])                            # (iblk,)
        return h, y

    h0 = h_scr[:]
    y0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h_fin, y = jax.lax.fori_loop(0, chunk, body, (h0, y0))
    h_scr[:] = h_fin
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _fin():
        hf_ref[0] = h_fin.astype(hf_ref.dtype)


def _largest_divisor(n: int, cap: int = 128) -> int:
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
                          Cm: jnp.ndarray, A: jnp.ndarray, h0: jnp.ndarray,
                          *, chunk: int = 64, interpret: bool = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``interpret=None`` resolves to interpret mode off-TPU and compiled
    mode on TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, inner = x.shape
    state = A.shape[1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    iblk = _largest_divisor(inner)
    n_chunks = t // chunk
    grid = (b, inner // iblk, n_chunks)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    seq_i = pl.BlockSpec((1, chunk, iblk), lambda bb, i, j: (bb, j, i))
    seq_s = pl.BlockSpec((1, chunk, state), lambda bb, i, j: (bb, j, 0))
    a_spec = pl.BlockSpec((1, iblk, state), lambda bb, i, j: (0, i, 0))
    h_spec = pl.BlockSpec((1, iblk, state), lambda bb, i, j: (bb, i, 0))

    a3 = A[None]  # (1, inner, state) so it blocks like the state
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_i, seq_i, seq_s, seq_s, a_spec, h_spec],
        out_specs=[seq_i, h_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, inner), x.dtype),
                   jax.ShapeDtypeStruct((b, inner, state), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((iblk, state), jnp.float32)],
        interpret=interpret,
    )(x, dt, Bm, Cm, a3, h0)
    return y, h_fin
