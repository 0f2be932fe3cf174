"""Mixture-of-experts FFN with **colibri dispatch** — the paper's technique
as a first-class framework feature.

Token→expert assignment is a contended-RMW problem: T·k requests racing for
E expert queues with bounded capacity. Classic implementations either
scatter-add with duplicate indices (serialized conflict resolution — the
LRSC retry analogue) or drop randomly on overflow. Colibri dispatch:

  * requests are linearized once by a stable sort (``core.dispatch``),
  * each request gets its FIFO queue position (Qnode depth) — oldest
    requests win under capacity pressure (``LRSCwait_q`` semantics,
    starvation-free in arrival order),
  * the dispatch table is built with a single commit per (expert, slot).

Distribution (hierarchical EP): experts shard over the intra-pod ``data``
axis (a2a stays on intra-pod ICI); each expert's FFN shards over ``model``
(TP); pods replicate experts and sync gradients over ``pod``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import dispatch as D
from repro.distributed.sharding import Policy
from repro.models import layers as L

Params = Dict[str, Any]


def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = L.split(key, 5)

    def experts(k, din, dout):
        std = din ** -0.5
        return (jax.random.normal(k, (e, din, dout), jnp.float32) * std).astype(dtype)

    p = {"router": L.dense_init(ks[0], d, e, jnp.float32),
         "w_gate": experts(ks[1], d, f),
         "w_up": experts(ks[2], d, f),
         "w_down": experts(ks[3], f, d)}
    return p


def shared_init(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    return L.mlp_init(key, cfg.d_model, m.d_ff_expert * m.num_shared_experts,
                      "silu", dtype)


def capacity_for(num_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    t_assign = num_tokens * m.top_k
    cap = int(math.ceil(t_assign * m.capacity_factor / m.num_experts))
    cap = max(cap, 8)
    cap = min(cap, t_assign)
    return int(-(-cap // 8) * 8) if cap >= 8 else cap   # round up to 8


def _route(cfg: ModelConfig, router_w, x_flat):
    """Router: probs, top-k ids/gates, aux load-balance loss (fp32)."""
    m = cfg.moe
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T,E)
    gates, ids = lax.top_k(probs, m.top_k)                      # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_e f_e * p_e
    t = x_flat.shape[0]
    f_e = D.histogram(ids.reshape(-1), m.num_experts).astype(jnp.float32) \
        / (t * m.top_k)
    p_e = probs.mean(0)
    aux = m.num_experts * jnp.sum(f_e * p_e)
    return ids, gates, aux


def _expert_ffn(w_gate, w_up, w_down, xbuf):
    """xbuf: (E, C, d) -> (E, C, d). Plain SwiGLU per expert."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xbuf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------------------
# Local path (single device / no mesh)
# ---------------------------------------------------------------------------

def _moe_local(cfg: ModelConfig, p: Params, x_flat) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m = cfg.moe
    t, d = x_flat.shape
    ids, gates, aux = _route(cfg, p["router"], x_flat)
    keys = ids.reshape(-1)                                      # (T*k,)
    cap = capacity_for(t, cfg)
    src, valid, disp = D.dispatch_indices(keys, m.num_experts, cap)
    token_of = src // m.top_k                                   # assignment -> token
    xbuf = jnp.take(x_flat, jnp.where(valid, token_of, 0), axis=0)
    xbuf = jnp.where(valid[..., None], xbuf, 0)                 # (E,C,d)
    ybuf = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], xbuf)
    y_assign = D.combine_from_slots(ybuf, keys, disp.queue_pos, disp.keep,
                                    gates.reshape(-1))
    y = y_assign.reshape(t, m.top_k, d).sum(1)
    return y.astype(x_flat.dtype), aux


# ---------------------------------------------------------------------------
# Sharded path (EP over "data", expert-TP over "model")
# ---------------------------------------------------------------------------

def _moe_sharded_body(cfg: ModelConfig, ep_axis: str, tp_axis: Optional[str],
                      router_w, w_gate, w_up, w_down, x_blk):
    """shard_map body. x_blk: (B_l, S, d) local tokens (replicated over tp).
    w_*: (E_l, d, f_l) local expert shards."""
    m = cfg.moe
    n_ep = lax.psum(1, ep_axis)
    b_l, s, d = x_blk.shape
    x_flat = x_blk.reshape(b_l * s, d)
    t = b_l * s
    ids, gates, aux = _route(cfg, router_w, x_flat)
    keys = ids.reshape(-1)
    cap = capacity_for(t, cfg)
    # --- enqueue: colibri ordered dispatch into the global expert queues ---
    src, valid, disp = D.dispatch_indices(keys, m.num_experts, cap)
    token_of = src // m.top_k
    xbuf = jnp.take(x_flat, jnp.where(valid, token_of, 0), axis=0)
    xbuf = jnp.where(valid[..., None], xbuf, 0)                 # (E, C, d)
    # --- serve: a2a tokens to their expert's owner (intra-pod ICI) ---
    xrecv = lax.all_to_all(xbuf, ep_axis, split_axis=0, concat_axis=0,
                           tiled=True)                          # (n_ep*E_l, C, d)
    e_l = m.num_experts // n_ep
    xrecv = xrecv.reshape(n_ep, e_l, cap, d).transpose(1, 0, 2, 3) \
                 .reshape(e_l, n_ep * cap, d)
    y_l = _expert_ffn(w_gate, w_up, w_down, xrecv)              # partial over f
    # --- commit: a2a the f-PARTIAL outputs back, combine, then ONE psum on
    # the combined (T,d) tokens. §Perf hillclimb #3: psum-before-a2a reduced
    # the full (E, n_ep·C, d) dispatch buffer (top_k·cf ≈ 10x the token
    # bytes); psum-after-combine reduces only (T, d). The a2a is unchanged
    # (partials are the same size), total collective bytes drop ~2x and the
    # psum term ~10x. Mathematically identical: gather/weighted-sum commute
    # with the sum over f-shards. ---
    y_l = y_l.reshape(e_l, n_ep, cap, d).transpose(1, 0, 2, 3) \
             .reshape(n_ep * e_l, cap, d)
    ybuf = lax.all_to_all(y_l, ep_axis, split_axis=0, concat_axis=0,
                          tiled=True)                           # (E, C, d)
    y_assign = D.combine_from_slots(ybuf, keys, disp.queue_pos, disp.keep,
                                    gates.reshape(-1))
    y = y_assign.reshape(t, m.top_k, d).sum(1)
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    y = y.reshape(b_l, s, d)
    return y.astype(x_blk.dtype), aux.reshape(1)


def moe_apply(cfg: ModelConfig, p: Params, x, policy: Policy
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d). Returns (y, aux_loss_scalar)."""
    b, s, d = x.shape
    if policy.mesh is None or policy.ep_axis is None:
        y, aux = _moe_local(cfg, p, x.reshape(b * s, d))
        return y.reshape(b, s, d), aux

    ep, tp = policy.ep_axis, policy.tp_axis
    dp = policy.dp_axes if len(policy.dp_axes) > 1 else policy.dp_axes[0]
    in_specs = (
        P(dp, None, None),                   # x
        P(None, None),                       # router (replicated)
        P(ep, None, tp), P(ep, None, tp),    # w_gate, w_up
        P(ep, tp, None),                     # w_down
    )
    out_specs = (P(dp, None, None), P(dp))
    body = partial(_moe_sharded_body, cfg, ep, tp)

    def f(x_, r_, wg_, wu_, wd_):
        return body(r_, wg_, wu_, wd_, x_)

    y, aux = jax.shard_map(
        f, mesh=policy.mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux.mean()
