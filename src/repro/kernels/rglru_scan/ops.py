"""Public RG-LRU scan op."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rglru_scan.kernel import rglru_scan_kernel


@partial(jax.jit, static_argnames=("block_c", "block_b", "block_w",
                                   "interpret"))
def rglru_scan(a, b, h0, block_c: int = 256, block_b: int = 8,
               block_w: int = 256, *, interpret: bool):
    """Linear recurrence h_t = a_t ⊙ h_{t-1} + b_t. a, b: (T,B,w); h0: (B,w)."""
    return rglru_scan_kernel(a, b, h0, block_c=block_c, block_b=block_b,
                             block_w=block_w, interpret=interpret)
