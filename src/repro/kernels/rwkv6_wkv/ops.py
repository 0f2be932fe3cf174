"""Public chunked WKV op."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rwkv6_wkv.kernel import wkv_chunked_kernel


@partial(jax.jit, static_argnames=("block_c", "interpret"))
def wkv_chunked(r, k, v, w, u, block_c: int = 64, *, interpret: bool):
    """Chunked-parallel RWKV-6 WKV. r,k,v,w: (BH,T,hd); u: (BH,hd)."""
    return wkv_chunked_kernel(r, k, v, w, u, block_c=block_c,
                              interpret=interpret)
