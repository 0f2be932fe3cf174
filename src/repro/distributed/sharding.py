"""Sharding policy: mesh axes, param partition rules, activation constraints.

Axis semantics
--------------
* ``data`` (+ ``pod`` when multi-pod): batch data parallelism; also the FSDP
  (ZeRO-3) weight-shard axis for ≥100B archs and the **expert-parallel (EP)**
  axis for MoE (intra-pod a2a — hierarchical EP; pods replicate experts and
  sync grads over ``pod``).
* ``model``: tensor parallelism (attention heads / FFN intermediate / vocab)
  and the per-expert FFN shard for MoE.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Policy:
    """Distribution policy threaded through model code.

    ``mesh is None`` ⇒ single-device (smoke tests); all constraints no-op and
    MoE uses the local (collective-free) path."""
    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ()          # ("pod","data") or ("data",)
    ep_axis: Optional[str] = None          # intra-pod EP axis ("data")
    tp_axis: Optional[str] = None          # "model"
    fsdp: bool = False
    use_pallas: bool = False
    sequence_parallel: bool = False

    def axis_size(self, name: Optional[str]) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.axis_size(a) for a in self.dp_axes])) or 1

    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def batch_spec(self, ndim: int) -> P:
        """Batch-leading activations: (B, ...) over dp axes."""
        if self.mesh is None:
            return P()
        return P(self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0],
                 *([None] * (ndim - 1)))


def make_policy(mesh: Optional[Mesh], cfg=None, use_pallas: bool = False) -> Policy:
    if mesh is None:
        return Policy(use_pallas=use_pallas)
    names = mesh.axis_names
    dp_axes = tuple(a for a in names if a in ("pod", "data"))
    return Policy(
        mesh=mesh,
        dp_axes=dp_axes,
        ep_axis="data" if "data" in names else None,
        tp_axis="model" if "model" in names else None,
        fsdp=bool(cfg and cfg.parallel.fsdp),
        use_pallas=use_pallas,
        sequence_parallel=bool(cfg and cfg.parallel.sequence_parallel),
    )


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------
# Each rule: (path regex, spec template). Template entries name a mesh axis
# role: "tp" / "fsdp" / None. Leading "L" marks the stacked-layer axis (never
# sharded). Dims whose size is not divisible by the axis size are silently
# replicated (best-effort rule, e.g. smollm's 9 heads).

_RULES = [
    # embeddings / heads
    (r"embed$",                      ("tp", "fsdp")),
    (r"pos_embed$",                  (None, "fsdp")),
    (r"lm_head$",                    ("fsdp", "tp")),
    # attention (flat (d, H*hd) layouts)
    (r"(attn|self_attn|cross_attn|enc.*attn)\.w[qkv]$", ("L", "fsdp", "tp")),
    (r"(attn|self_attn|cross_attn|enc.*attn)\.wo$",     ("L", "tp", "fsdp")),
    (r"attn\.b[qkv]$",               ("L", "tp")),
    # MLA
    (r"attn\.w_dq$",                 ("L", "fsdp", None)),
    (r"attn\.w_uq$",                 ("L", "fsdp", "tp")),
    (r"attn\.w_dkv$",                ("L", "fsdp", None)),
    (r"attn\.w_kr$",                 ("L", "fsdp", None)),
    (r"attn\.w_uk$",                 ("L", "tp", None, "fsdp")),
    (r"attn\.w_uv$",                 ("L", "tp", "fsdp", None)),
    # dense MLPs
    (r"mlp\.w_(gate|up)$",           ("L", "fsdp", "tp")),
    (r"mlp\.w_down$",                ("L", "tp", "fsdp")),
    (r"mlp\.b_up$",                  ("L", "tp")),
    # MoE: experts (E, d, f): EP over data, per-expert TP over model
    (r"moe\.w_(gate|up)$",           ("L", "ep", None, "tp")),
    (r"moe\.w_down$",                ("L", "ep", "tp", None)),
    (r"moe\.router$",                ("L", "fsdp", None)),
    (r"shared\.w_(gate|up)$",        ("L", "fsdp", "tp")),
    (r"shared\.w_down$",             ("L", "tp", "fsdp")),
    # recurrent blocks
    (r"(rglru|rwkv)\..*w_(in|gate|r|k|v|g|out|o)$", ("L", "fsdp", "tp")),
    (r"(rglru|rwkv)\..*w_(down|proj)$",             ("L", "tp", "fsdp")),
]


def _axis_for(role: Optional[str], policy: Policy) -> Optional[str]:
    if role == "tp":
        return policy.tp_axis
    if role == "ep":
        return policy.ep_axis
    if role == "fsdp":
        # FSDP shards over the innermost dp axis ("data")
        return "data" if (policy.fsdp and policy.mesh is not None
                          and "data" in policy.mesh.axis_names) else None
    return None


def spec_for(path: str, shape: Tuple[int, ...], policy: Policy,
             stacked: bool) -> P:
    """Best-effort PartitionSpec for a param at ``path`` with ``shape``."""
    if policy.mesh is None:
        return P()
    for pat, template in _RULES:
        if re.search(pat, path):
            tpl = list(template)
            if tpl and tpl[0] == "L":
                tpl = tpl[1:]
                if stacked:
                    tpl = [None] + tpl
            elif stacked:
                tpl = [None] + tpl
            tpl = (tpl + [None] * len(shape))[: len(shape)]
            out = []
            for dim, role in zip(shape, tpl):
                ax = _axis_for(role, policy)
                if ax is not None and dim % policy.axis_size(ax) == 0 \
                        and dim >= policy.axis_size(ax):
                    out.append(ax)
                else:
                    out.append(None)
            return P(*out)
    return P()  # norms, biases, small vectors: replicated


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def param_specs(params: Params, policy: Policy) -> Params:
    """PartitionSpec pytree matching ``params``. Stacked-layer arrays are
    detected by path prefix ('blocks.' / 'enc_blocks.' / 'segments.')."""
    def leaf_spec(path, leaf):
        ps = _path_str(path)
        stacked = any(s in ps for s in ("blocks.", "segments.", "enc_blocks."))
        return spec_for(ps, np.shape(leaf), policy, stacked)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def shardings_of(tree_specs: Params, mesh: Mesh) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def cache_specs(cache: Params, policy: Policy) -> Params:
    """KV caches / recurrent states. Leaves are stacked over layers:
    (L, B, S, heads, hd) — shard the BATCH dim (axis 1) over dp and the
    seq/heads dim (axis 2) over model when divisible. Batch < dp size
    (long_500k B=1) replicates."""
    if policy.mesh is None:
        return jax.tree.map(lambda _: P(), cache)
    dp = policy.dp_axes if len(policy.dp_axes) > 1 else policy.dp_axes[0]
    dp_size = policy.dp_size
    tp = policy.tp_axis
    tp_size = policy.axis_size(tp)

    def leaf(x):
        shape = np.shape(x)
        spec = [None] * len(shape)
        if len(shape) >= 2 and shape[1] % dp_size == 0 and shape[1] >= dp_size:
            spec[1] = dp
        if tp and len(shape) >= 4 and shape[2] % tp_size == 0 \
                and shape[2] >= tp_size:
            spec[2] = tp
        return P(*spec)
    return jax.tree.map(leaf, cache)
