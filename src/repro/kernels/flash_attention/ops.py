"""Public flash-attention op: GQA fold/unfold around the Pallas kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_folded
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, S, H, Dh); k/v: (B, S, KV, Dh) -> (B, S, H, Dh)."""
    b, s, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    # fold: (B, S, KV, G, Dh) -> (B, KV, S, G, Dh) -> (B*KV, S*G, Dh)
    qf = q.reshape(b, s, kv, g, dh).transpose(0, 2, 1, 3, 4).reshape(b * kv, s * g, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, s, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, s, dh)
    of = flash_attention_folded(qf, kf, vf, group=g, causal=causal,
                                window=window, block_q=block_q,
                                block_k=block_k, interpret=interpret)
    return (of.reshape(b, kv, s, g, dh).transpose(0, 2, 1, 3, 4)
            .reshape(b, s, h, dh))


def attention(q, k, v, *, causal=True, window=None, impl: str = "pallas"):
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window)
    return attention_ref(q, k, v, causal=causal, window=window)
