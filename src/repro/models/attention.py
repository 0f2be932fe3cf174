"""Attention blocks: GQA (full / sliding-window / decode) and MLA (DeepSeek).

All softmax math in fp32. Long sequences use a blocked online-softmax
(flash-style) pure-JAX path so prefill_32k never materialises S×S scores;
the Pallas kernel (repro.kernels.flash_attention) is the TPU hot path and is
validated against these functions.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _expand_kv(k: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    """(B,S,KV,hd) -> (B,S,H,hd) by repeating each kv head G times."""
    b, s, kv, hd = k.shape
    if kv == num_q_heads:
        return k
    g = num_q_heads // kv
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, g, hd)).reshape(
        b, s, num_q_heads, hd)


def naive_attention(q, k, v, *, causal: bool, q_pos=None, kv_pos=None,
                    window: int = 0, scale: Optional[float] = None):
    """Reference O(S^2)-memory attention. q:(B,Sq,H,hd) k,v:(B,Skv,H,hd)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    if q_pos is None:
        q_pos = jnp.arange(sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blocked_attention(q, k, v, *, causal: bool, q_block: int = 1024,
                      kv_block: int = 1024, scale: Optional[float] = None,
                      q_offset: int = 0):
    """Flash-style online-softmax attention, O(S·block) memory.

    q: (B,Sq,H,hd); k,v: (B,Skv,H,hd). ``q_offset`` shifts query positions
    (used when Sq != Skv in cached generation)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5

    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    # pad to block multiples
    pq = (-sq) % qb
    pk = (-skv) % kb
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // qb, kp.shape[1] // kb
    qc = qp.reshape(b, nq, qb, h, hd).transpose(1, 0, 2, 3, 4)    # (nq,B,qb,H,hd)
    kc = kp.reshape(b, nk, kb, h, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nk, kb, h, hd).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(nq * qb).reshape(nq, qb) + q_offset
    kv_pos = jnp.arange(nk * kb).reshape(nk, kb)
    kv_valid = kv_pos < skv

    @partial(jax.checkpoint, prevent_cse=False)
    def q_chunk(args):
        # checkpointed (flash-attention style): backward recomputes block
        # scores from q/k instead of stacking per-block softmax residuals.
        iq, qi = args                                             # qi: (B,qb,H,hd)
        qi32 = qi.astype(jnp.float32) * scale

        def kv_step(carry, args2):
            m, l, acc = carry
            ik, ki, vi, kpos, kval = args2
            s = jnp.einsum("bqhd,bkhd->bhqk", qi32, ki.astype(jnp.float32))
            mask = kval[None, :]
            if causal:
                mask = mask & (q_pos[iq][:, None] >= kpos[None, :])
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))                     # (B,H,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qb), jnp.float32)
        a0 = jnp.zeros((b, h, qb, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kc, vc, kv_pos, kv_valid))
        out = acc / jnp.maximum(l, 1e-30)[..., None]              # (B,H,qb,hd)
        return out.transpose(0, 2, 1, 3)                          # (B,qb,H,hd)

    outs = lax.map(q_chunk, (jnp.arange(nq), qc))                 # (nq,B,qb,H,hd)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * qb, h, hd)
    return out[:, :sq].astype(q.dtype)


def sliding_window_attention(q, k, v, *, window: int,
                             scale: Optional[float] = None,
                             q_sub: int = 256):
    """Banded causal attention: each query chunk of size W attends to its own
    and the previous chunk only — exact for window ≤ W, O(S·W/q_sub) live
    memory (queries sub-chunked, bodies checkpointed)."""
    b, s, h, hd = q.shape
    w = min(window, s)
    p = (-s) % w
    qp = jnp.pad(q, ((0, 0), (0, p), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, p), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, p), (0, 0), (0, 0)))
    n = qp.shape[1] // w
    qc = qp.reshape(b, n, w, h, hd).transpose(1, 0, 2, 3, 4)   # (n,B,w,H,hd)
    kc = kp.reshape(b, n, w, h, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, n, w, h, hd).transpose(1, 0, 2, 3, 4)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:1]), kc[:-1]], axis=0)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:1]), vc[:-1]], axis=0)
    k2 = jnp.concatenate([kprev, kc], axis=2)                  # (n,B,2w,H,hd)
    v2 = jnp.concatenate([vprev, vc], axis=2)
    scale = scale if scale is not None else hd ** -0.5
    sub = min(q_sub, w)
    nsub = w // sub if w % sub == 0 else (w + (-w) % sub) // sub
    psub = nsub * sub - w

    @jax.checkpoint
    def chunk(args):
        ci, qi, ki, vi = args          # qi: (B,w,H,hd); ki/vi: (B,2w,H,hd)
        qi = jnp.pad(qi, ((0, 0), (0, psub), (0, 0), (0, 0)))
        qs = qi.reshape(b, nsub, sub, h, hd).transpose(1, 0, 2, 3, 4)

        def sub_chunk(args2):
            si, qj = args2                                     # (B,sub,H,hd)
            lg = jnp.einsum("bqhd,bkhd->bhqk", qj.astype(jnp.float32),
                            ki.astype(jnp.float32)) * scale
            gq = ci * w + si * sub + jnp.arange(sub)[:, None]
            gk = ci * w - w + jnp.arange(2 * w)[None, :]
            mask = (gq >= gk) & (gq - gk < window) & (gk >= 0) & (gk < s)
            lg = jnp.where(mask[None, None], lg, NEG_INF)
            pr = jax.nn.softmax(lg, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", pr, vi.astype(jnp.float32))
            return o.astype(q.dtype)

        outs = lax.map(sub_chunk, (jnp.arange(nsub), qs))      # (nsub,B,sub,..)
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, nsub * sub, h, hd)[:, :w]

    outs = lax.map(chunk, (jnp.arange(n), qc, k2, v2))         # (n,B,w,H,hd)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n * w, h, hd)
    return out[:, :s].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: Optional[float] = None):
    """Single-token decode. q:(B,1,H,hd); caches:(B,S,H,hd); pos:(B,) current
    write position (keys at index <= pos are valid)."""
    b, _, h, hd = q.shape
    s = k_cache.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k_cache,
                        preferred_element_type=jnp.float32)[:, :, 0]  # (B,H,S)
    idx = jnp.arange(s)[None, :]
    mask = idx <= pos[:, None]
    if window:
        mask &= idx > pos[:, None] - window
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)                           # (B,1,H,hd)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = L.split(key, 4)
    p = {"wq": L.dense_init(k1, d, h * hd, dtype),
         "wk": L.dense_init(k2, d, kv * hd, dtype),
         "wv": L.dense_init(k3, d, kv * hd, dtype),
         "wo": L.dense_init(k4, h * hd, d, dtype)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _qkv(cfg: ModelConfig, p: Params, x):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


def _head_shard(policy, q, ke, ve):
    """§Perf hillclimb #1: pad heads to a TP multiple and pin q/k/v to a
    head-sharded layout BEFORE the attention chunk loops. Without this, head
    counts not divisible by the model axis (qwen2: 28 heads vs TP=16) make
    the SPMD partitioner reshard K/V inside the flash chunk loops — per-
    chunk gathers multiplied by loop trip counts (measured: 17.6 s of
    collectives in one qwen2 prefill_32k step). Padded heads are sliced off
    before wo; the extra FLOPs are ≤ +(tp-1)/H of attention."""
    if policy is None or policy.mesh is None or policy.tp_axis is None:
        return q, ke, ve, q.shape[2]
    tp = policy.axis_size(policy.tp_axis)
    h = q.shape[2]
    h_pad = -(-h // tp) * tp
    if h_pad != h:
        pad = ((0, 0), (0, 0), (0, h_pad - h), (0, 0))
        q, ke, ve = jnp.pad(q, pad), jnp.pad(ke, pad), jnp.pad(ve, pad)
    dp = policy.dp_axes if len(policy.dp_axes) > 1 else policy.dp_axes[0]
    spec = (dp, None, policy.tp_axis, None)
    return (policy.constrain(q, *spec), policy.constrain(ke, *spec),
            policy.constrain(ve, *spec), h)


def _cp_attention(policy, cfg, q, k, v, *, causal: bool, scale: float):
    """Context-parallel attention: shard_map over the model axis with
    sequence-sharded queries and replicated (unexpanded) K/V."""
    from jax.sharding import PartitionSpec as P
    dp = policy.dp_axes if len(policy.dp_axes) > 1 else policy.dp_axes[0]
    tp = policy.tp_axis

    def body(qb, kb, vb):
        ke = _expand_kv(kb, cfg.num_heads)
        ve = _expand_kv(vb, cfg.num_heads)
        off = lax.axis_index(tp) * qb.shape[1]
        return blocked_attention(qb, ke, ve, causal=causal, scale=scale,
                                 q_offset=off)

    return jax.shard_map(
        body, mesh=policy.mesh,
        in_specs=(P(dp, tp, None, None), P(dp, None, None, None),
                  P(dp, None, None, None)),
        out_specs=P(dp, tp, None, None), check_vma=False)(q, k, v)


def gqa_apply(cfg: ModelConfig, p: Params, x, positions, *, causal=True,
              window: int = 0, rope: bool = True,
              kv_out: bool = False, policy=None):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q, k, v = _qkv(cfg, p, x)
    if rope and cfg.partial_rotary_factor > 0:
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    scale = q.shape[-1] ** -0.5
    if policy is not None and policy.mesh is not None \
            and policy.sequence_parallel and not window \
            and q.shape[1] % policy.axis_size(policy.tp_axis) == 0:
        # §Perf hillclimb: context-parallel attention via shard_map. q stays
        # SEQUENCE-sharded over the model axis (head divisibility is
        # irrelevant); the small UNEXPANDED GQA K/V are gathered once per
        # layer; expansion + flash chunking run locally per shard. A plain
        # with_sharding_constraint is NOT enough here: the chunk scan
        # iterates the sharded axis, so the partitioner would re-gather
        # every chunk (measured 149 GB/step on qwen2 prefill).
        out = _cp_attention(policy, cfg, q, k, v, causal=causal, scale=scale)
    else:
        ke = _expand_kv(k, cfg.num_heads)
        ve = _expand_kv(v, cfg.num_heads)
        q, ke, ve, h_real = _head_shard(policy, q, ke, ve)
        if window:
            out = sliding_window_attention(q, ke, ve, window=window,
                                           scale=scale)
        else:
            out = blocked_attention(q, ke, ve, causal=causal, scale=scale)
        out = out[:, :, :h_real]
    b, s = x.shape[:2]
    out = out.reshape(b, s, -1) @ p["wo"]
    return (out, (k, v)) if kv_out else (out, None)


def gqa_decode(cfg: ModelConfig, p: Params, x, cache: Params, pos, *,
               window: int = 0, rope: bool = True):
    """One-token decode with KV cache. x:(B,1,d); pos:(B,). Returns
    (out, new_cache). Cache k/v: (B,S,KV,hd) (ring buffer of size W for
    sliding-window layers)."""
    q, k, v = _qkv(cfg, p, x)
    if rope and cfg.partial_rotary_factor > 0:
        q = L.apply_rope(q, pos[:, None], cfg.rope_theta, cfg.partial_rotary_factor)
        k = L.apply_rope(k, pos[:, None], cfg.rope_theta, cfg.partial_rotary_factor)
    s_cache = cache["k"].shape[1]
    slot = pos % s_cache if window else pos

    def upd(c, new):
        return jax.vmap(
            lambda cb, nb, pb: lax.dynamic_update_slice(cb, nb, (pb, 0, 0))
        )(c, new, slot)

    k_cache = upd(cache["k"], k.astype(cache["k"].dtype))
    v_cache = upd(cache["v"], v.astype(cache["v"].dtype))
    ke = _expand_kv(k_cache, cfg.num_heads)
    ve = _expand_kv(v_cache, cfg.num_heads)
    if window:
        # ring buffer: entry at index i holds global position
        # floor((pos - i) / W) * W + i -> valid iff within window of pos.
        b = x.shape[0]
        idx = jnp.arange(s_cache)[None, :]
        age = (slot[:, None] - idx) % s_cache                      # 0..W-1 steps ago
        mask = age <= jnp.minimum(pos, s_cache - 1)[:, None]
        logits = jnp.einsum(
            "bqhd,bkhd->bhk", q * cfg.resolved_head_dim ** -0.5, ke,
            preferred_element_type=jnp.float32)
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        pr = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhk,bkhd->bhd", pr.astype(ve.dtype), ve,
                         preferred_element_type=jnp.float32)
        out = out[:, None].astype(x.dtype)
    else:
        out = decode_attention(q, ke, ve, pos)
    b, s = x.shape[:2]
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


def gqa_cache_init(cfg: ModelConfig, batch: int, seq: int, dtype) -> Params:
    hd = cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, seq, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, seq, cfg.num_kv_heads, hd), dtype)}


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg: ModelConfig, dtype) -> Params:
    d, hd, h = cfg.d_model, cfg.resolved_head_dim, cfg.num_heads
    k1, k2, k3, k4 = L.split(key, 4)
    return {"wq": L.dense_init(k1, d, h * hd, dtype),
            "wk": L.dense_init(k2, d, h * hd, dtype),
            "wv": L.dense_init(k3, d, h * hd, dtype),
            "wo": L.dense_init(k4, h * hd, d, dtype)}


def cross_attn_apply(cfg: ModelConfig, p: Params, x, enc_kv=None, enc=None):
    """x:(B,S,d); enc:(B,Se,d) or precomputed enc_kv=(k,v)."""
    b, s, _ = x.shape
    hd, h = cfg.resolved_head_dim, cfg.num_heads
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    if enc_kv is None:
        se = enc.shape[1]
        k = (enc @ p["wk"]).reshape(b, se, h, hd)
        v = (enc @ p["wv"]).reshape(b, se, h, hd)
    else:
        k, v = enc_kv
    out = blocked_attention(q, k, v, causal=False)
    return out.reshape(b, s, -1) @ p["wo"], (k, v)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = L.split(key, 8)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": L.dense_init(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": L.rmsnorm_init(m.q_lora_rank, dtype),
        "w_uq": L.dense_init(ks[1], m.q_lora_rank, h * qk_head, dtype),
        "w_dkv": L.dense_init(ks[2], d, m.kv_lora_rank, dtype),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dtype),
        "w_kr": L.dense_init(ks[3], d, m.qk_rope_head_dim, dtype),
        # up-projections stored per-head for the absorbed decode path
        "w_uk": (jax.random.normal(ks[4], (h, m.qk_nope_head_dim, m.kv_lora_rank),
                                   jnp.float32) * m.kv_lora_rank ** -0.5).astype(dtype),
        "w_uv": (jax.random.normal(ks[5], (h, m.kv_lora_rank, m.v_head_dim),
                                   jnp.float32) * m.kv_lora_rank ** -0.5).astype(dtype),
        "wo": L.dense_init(ks[6], h * m.v_head_dim, d, dtype),
    }


def _mla_q(cfg, p, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    cq = L.rmsnorm(p["q_norm"], x @ p["w_dq"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(cfg: ModelConfig, p: Params, x, positions):
    """Full-sequence MLA (train / prefill). Returns (out, (c_kv, k_rope))."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv = L.rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)   # (B,S,r)
    k_rope = L.apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                          cfg.rope_theta)                          # (B,S,1,rr)
    k_nope = jnp.einsum("bsr,hdr->bshd", c_kv, p["w_uk"])          # (B,S,H,nope)
    v = jnp.einsum("bsr,hrv->bshv", c_kv, p["w_uv"])               # (B,S,H,v)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, k.shape[-1] - v.shape[-1])))
    out = blocked_attention(q, k, vp, causal=True, scale=scale)
    out = out[..., : m.v_head_dim]
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, (c_kv, k_rope[:, :, 0])


def mla_decode(cfg: ModelConfig, p: Params, x, cache: Params, pos):
    """Absorbed-matrix decode: attention runs in the latent space; the cache
    holds only (c_kv, k_rope) — the MLA memory saving."""
    m = cfg.mla
    b = x.shape[0]
    q_nope, q_rope = _mla_q(cfg, p, x, pos[:, None])
    c_new = L.rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)  # (B,1,r)
    kr_new = L.apply_rope((x @ p["w_kr"])[:, :, None, :], pos[:, None],
                          cfg.rope_theta)[:, :, 0]                 # (B,1,rr)

    def upd(c, new):
        return jax.vmap(lambda cb, nb, pb: lax.dynamic_update_slice(
            cb, nb, (pb, 0)))(c, new.astype(c.dtype), pos)

    ckv = upd(cache["c_kv"], c_new)                                # (B,S,r)
    krope = upd(cache["k_rope"], kr_new)                           # (B,S,rr)
    # absorbed scores
    q_lat = jnp.einsum("bqhd,hdr->bhr", q_nope, p["w_uk"],
                       preferred_element_type=jnp.float32)         # (B,H,r)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s_lat = jnp.einsum("bhr,bsr->bhs", q_lat.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bsd->bhs", q_rope, krope,
                        preferred_element_type=jnp.float32)
    logits = (s_lat + s_rope) * scale
    idx = jnp.arange(ckv.shape[1])[None, :]
    logits = jnp.where((idx <= pos[:, None])[:, None], logits, NEG_INF)
    pr = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pr.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)           # (B,H,r)
    out = jnp.einsum("bhr,hrv->bhv", ctx.astype(p["w_uv"].dtype), p["w_uv"],
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, -1).astype(x.dtype) @ p["wo"]
    return out, {"c_kv": ckv, "k_rope": krope}


def mla_cache_init(cfg: ModelConfig, batch: int, seq: int, dtype) -> Params:
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, seq, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq, m.qk_rope_head_dim), dtype)}
