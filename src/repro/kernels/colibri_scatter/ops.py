"""Public op: colibri_scatter_add = sort-linearize (enqueue) + kernel commit."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.colibri_scatter.kernel import scatter_commit


@partial(jax.jit, static_argnames=("num_bins", "block_t", "block_bins",
                                   "interpret"))
def colibri_scatter_add(keys: jnp.ndarray, vals: jnp.ndarray, num_bins: int,
                        block_t: int = 512, block_bins: int = 128, *,
                        interpret: bool) -> jnp.ndarray:
    """Retry-free scatter-add: sort once (linearization point), commit once
    per bin. keys: (T,) int32 in [0, num_bins); vals: (T, d) or (T,)."""
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[:, None]
    order = jnp.argsort(keys, stable=True)
    out = scatter_commit(keys[order], vals[order], num_bins,
                         block_t=block_t, block_bins=block_bins,
                         interpret=interpret)
    return out[:, 0] if squeeze else out


@partial(jax.jit, static_argnames=("num_bins", "interpret"))
def colibri_histogram(keys: jnp.ndarray, num_bins: int, *,
                      interpret: bool) -> jnp.ndarray:
    """The paper's benchmark op as a kernel."""
    return colibri_scatter_add(
        keys, jnp.ones((keys.shape[0],), jnp.float32), num_bins,
        interpret=interpret).astype(jnp.int32)
