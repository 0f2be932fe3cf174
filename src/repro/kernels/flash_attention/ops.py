"""Public flash-attention op with GQA head layout handling."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool) -> jnp.ndarray:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H % KV == 0.
    Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, -1, hd)
    out = flash_attention_kernel(qf, kf, vf, causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
