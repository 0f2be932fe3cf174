"""Public grouped-matmul op."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul.kernel import grouped_matmul_kernel


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                   "interpret"))
def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, block_c: int = 128,
                   block_f: int = 128, block_d: int = 256, *,
                   interpret: bool) -> jnp.ndarray:
    """MoE expert GEMM over dispatch buffers: (E,C,d) @ (E,d,f) -> (E,C,f)."""
    return grouped_matmul_kernel(x, w, block_c=block_c, block_f=block_f,
                                 block_d=block_d, interpret=interpret)
