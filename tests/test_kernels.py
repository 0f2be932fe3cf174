"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
on TPU the same pallas_call lowers to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.colibri_scatter import (colibri_histogram,
                                           colibri_scatter_add)
from repro.kernels.colibri_scatter.ref import scatter_add_ref

KEY = jax.random.PRNGKey(0)


def keys(n):
    return jax.random.split(KEY, n)


# ---------------------------------------------------------------------------
# colibri_scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,bins,d", [(100, 7, 1), (1000, 64, 8),
                                      (2048, 300, 16), (513, 1, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_colibri_scatter_sweep(t, bins, d, dtype):
    k1, k2 = keys(2)
    ks = jax.random.randint(k1, (t,), 0, bins)
    vs = jax.random.normal(k2, (t, d), dtype)
    out = colibri_scatter_add(ks, vs, bins, interpret=True)
    ref = scatter_add_ref(ks, vs.astype(jnp.float32), bins)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol * 10)


def test_colibri_scatter_block_shapes():
    """Result must be block-size independent (two-phase commit correctness)."""
    k1, k2 = keys(2)
    ks = jax.random.randint(k1, (777,), 0, 50)
    vs = jax.random.normal(k2, (777, 4))
    a = colibri_scatter_add(ks, vs, 50, block_t=128, block_bins=32,
                            interpret=True)
    b = colibri_scatter_add(ks, vs, 50, block_t=512, block_bins=128,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


@pytest.mark.parametrize("t,bins", [(100, 7), (1000, 64), (513, 1),
                                    (2048, 300)])
def test_colibri_histogram_parity(t, bins):
    """The paper's benchmark op vs the ref commit and np.bincount."""
    ks = jax.random.randint(keys(1)[0], (t,), 0, bins)
    out = np.asarray(colibri_histogram(ks, bins, interpret=True))
    ref = np.asarray(scatter_add_ref(
        ks, jnp.ones((t, 1), jnp.float32), bins))[:, 0].astype(np.int32)
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(
        out, np.bincount(np.asarray(ks), minlength=bins))


def test_trace_latency_hist_matches_engine():
    """The kernel's product caller: folding the exact recorded waits
    onto the engine's geometric bins reproduces the in-scan ``lat_hist``
    accumulator count for count (both see every retirement once)."""
    from repro.core import metrics
    from repro.core.sim import SimParams, execute
    res = execute(SimParams(protocol="colibri", n_cores=32, n_addrs=4,
                            cycles=4000, record_trace=True))
    hk = metrics.trace_latency_hist(res, interpret=True)
    np.testing.assert_array_equal(hk, np.asarray(res["lat_hist"]))
    np.testing.assert_array_equal(
        hk, metrics.trace_latency_hist(res))
