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
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.rwkv6_wkv import wkv_chunked
from repro.kernels.rwkv6_wkv.ref import wkv_ref

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


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,skv,h,kv,hd", [
    (2, 128, 128, 4, 4, 64),
    (1, 200, 200, 4, 2, 32),      # GQA + non-multiple seq
    (2, 64, 256, 2, 1, 64),       # MQA, cross lengths
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, skv, h, kv, hd, causal, dtype):
    if causal and sq != skv:
        pytest.skip("causal requires sq == skv in this test")
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (b, sq, h, hd), dtype)
    k = jax.random.normal(k2, (b, skv, kv, hd), dtype)
    v = jax.random.normal(k3, (b, skv, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    g = h // kv
    ke = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, skv, hd)
    ve = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, skv, hd)
    qe = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    ref = attention_ref(qe, ke, ve, causal=causal).reshape(b, h, sq, hd
                                                           ).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 5)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,c,d,f", [(4, 64, 128, 256), (8, 100, 96, 64),
                                     (1, 256, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(e, c, d, f, dtype):
    k1, k2 = keys(2)
    x = jax.random.normal(k1, (e, c, d), dtype)
    w = jax.random.normal(k2, (e, d, f), dtype)
    out = grouped_matmul(x, w, block_c=64, block_f=64, block_d=64,
                         interpret=True)
    ref = grouped_matmul_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 10)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,t,hd", [(2, 64, 32), (4, 130, 64), (1, 32, 16)])
def test_wkv_chunked_sweep(bh, t, hd):
    k1, k2, k3, k4, k5 = keys(5)
    r = jax.random.normal(k1, (bh, t, hd)) * 0.5
    k = jax.random.normal(k2, (bh, t, hd)) * 0.5
    v = jax.random.normal(k3, (bh, t, hd))
    # realistic rwkv6 decay: w = exp(-exp(x)), x ~ N(-1.5, 1)
    w = jnp.exp(-jnp.exp(jax.random.normal(k4, (bh, t, hd)) - 1.5))
    u = jax.random.normal(k5, (bh, hd)) * 0.1
    out = wkv_chunked(r, k, v, w, u, block_c=32, interpret=True)
    ref = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_wkv_chunk_size_invariance():
    k1, k2, k3, k4, k5 = keys(5)
    bh, t, hd = 2, 96, 32
    r = jax.random.normal(k1, (bh, t, hd)) * 0.5
    k = jax.random.normal(k2, (bh, t, hd)) * 0.5
    v = jax.random.normal(k3, (bh, t, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(k4, (bh, t, hd)) - 1.5))
    u = jax.random.normal(k5, (bh, hd)) * 0.1
    a = wkv_chunked(r, k, v, w, u, block_c=16, interpret=True)
    b = wkv_chunked(r, k, v, w, u, block_c=48, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,b,w", [(64, 2, 128), (100, 3, 60), (256, 1, 256)])
def test_rglru_scan_sweep(t, b, w):
    k1, k2, k3 = keys(3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (t, b, w)) + 2.0)  # decay ~ (0,1)
    x = jax.random.normal(k2, (t, b, w)) * 0.3
    h0 = jax.random.normal(k3, (b, w))
    out = rglru_scan(a, x, h0, block_c=32, block_b=2, block_w=64,
                     interpret=True)
    ref = rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_rglru_matches_model_block():
    """The kernel agrees with the model's associative-scan path on the same
    gate math (hillclimb swap-in safety)."""
    from repro.configs import get_config
    from repro.models import rglru as RG
    cfg = get_config("recurrentgemma-2b-smoke")
    p = RG.rglru_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model)) * 0.5
    state = RG.state_init(cfg, 2)
    out_model, _ = RG.rglru_apply(cfg, p, x, state)
    # recompute via the kernel on the same a/b streams
    y, _ = RG._conv1d_causal(x @ p["w_in"], p["conv_w"], p["conv_b"],
                             state["conv"])
    a, b = RG._gates(p, y.astype(jnp.float32))
    h = rglru_scan(a.transpose(1, 0, 2), b.transpose(1, 0, 2), state["h"],
                   block_c=16, interpret=True).transpose(1, 0, 2)
    gate = jax.nn.gelu(x @ p["w_gate"], approximate=True)
    out_kernel = (h.astype(x.dtype) * gate) @ p["w_proj"]
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=2e-4, atol=2e-4)
