import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent for every
(architecture × input shape × mesh) cell without real hardware.

For each cell:
    lowered  = jax.jit(step, in_shardings=..., donate...).lower(*specs)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # per-device bytes: the fits-proof
    print(compiled.cost_analysis())     # per-device FLOPs/bytes for §Roofline

plus the collective-byte parse of ``compiled.as_text()`` and the scan-body
correction compiles (see hlo_analysis). Results land in
``reports/dryrun/<arch>__<shape>__<mesh>.json`` — EXPERIMENTS.md §Dry-run
and benchmarks/roofline.py read from there.

NOTE the XLA_FLAGS line above MUST precede any jax import (device count is
locked at first init); smoke tests and benches see 1 device because only
this module sets the flag.
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import ARCH_NAMES, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed import cache_specs, make_policy, param_specs, shardings_of
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.train import batch_shardings, make_train_step, opt_state_shardings
from repro.models import build, input_specs
from repro.models import transformer as TF

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def _sds_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _abstract_params(model):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, opt_cfg):
    """Returns (step_fn, arg_sds tuple, in_shardings tuple, meta)."""
    policy = make_policy(mesh, cfg)
    model = build(cfg)
    params = _abstract_params(model)
    pshard = shardings_of(param_specs(params, policy), mesh)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        opt = jax.eval_shape(lambda p: optim.init(opt_cfg, p), params)
        oshard = opt_state_shardings(opt, params, policy)
        step = make_train_step(model, opt_cfg, policy,
                               cfg.parallel.accum_steps,
                               cfg.parallel.grad_accum_dtype)
        bshard = batch_shardings(specs, policy)
        return step, (params, opt, specs), (pshard, oshard, bshard), (0, 1)

    if shape.kind == "prefill":
        def step(p, batch):
            hidden, cache = model.prefill(p, batch, shape.seq_len, policy)
            return hidden[:, -1:], cache       # last-token hidden + full cache
        bshard = batch_shardings(specs, policy)
        return step, (params, specs), (pshard, bshard), ()

    # decode: one new token against a seq_len cache
    cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          cache_specs(cache, policy),
                          is_leaf=lambda x: isinstance(x, P))

    def step(p, c, batch):
        return model.decode_step(p, c, batch["tokens"], batch["pos"], policy)

    bshard = batch_shardings(specs, policy)
    return step, (params, cache, specs), (pshard, cshard, bshard), (1,)


def _segment_plan(cfg: ModelConfig):
    segs = TF.plan_segments(cfg)
    if cfg.encoder is not None:
        segs = segs + [((("enc", "enc"),), cfg.encoder.num_layers)]
    return segs


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             report_dir: str = REPORT_DIR, verbose: bool = True
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "kind": shape.kind}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        out["status"] = "skipped"
        out["reason"] = reason
        _write(out, report_dir)
        return out

    mesh = make_production_mesh(multi_pod=multi_pod)
    opt_cfg = optim.AdamWConfig(state_dtype=cfg.parallel.opt_state_dtype)
    t0 = time.time()
    try:
        step, args, shardings, donate = build_cell(cfg, shape, mesh, opt_cfg)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        out["memory"] = H.memory_stats(compiled)
        out["cost"] = H.cost_stats(compiled)
        text = compiled.as_text()
        out["collectives"] = dict(H.collective_bytes_corrected(text))
        out["collectives"]["counts"] = H.collective_bytes(text)["counts"]
        out["lower_s"] = round(t_lower, 1)
        out["compile_s"] = round(t_compile, 1)
        out["segments"] = [[list(map(str, u)), r]
                           for u, r in _segment_plan(cfg)]
        out["status"] = "ok"
        if verbose:
            mem = out["memory"]
            print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                  f"compile={t_compile:.0f}s "
                  f"peak/device={mem.get('peak_bytes', 0)/2**30:.2f}GiB "
                  f"flops/device={out['cost']['flops']:.3e} "
                  f"coll={out['collectives']['total']/2**20:.1f}MiB")
            print("  memory_analysis:", compiled.memory_analysis())
            print("  cost_analysis: flops=%.3e bytes=%.3e" % (
                out["cost"]["flops"], out["cost"]["bytes_accessed"]))
    except Exception as e:  # noqa: BLE001 — record the failure, don't mask it
        out["status"] = "failed"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] FAILED: "
                  f"{out['error']}")
    _write(out, report_dir)
    return out


def _write(out: Dict[str, Any], report_dir: str):
    os.makedirs(report_dir, exist_ok=True)
    name = f"{out['arch']}__{out['shape']}__{out['mesh']}.json"
    with open(os.path.join(report_dir, name), "w") as f:
        json.dump(out, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) on this mesh")
    ap.add_argument("--report-dir", default=REPORT_DIR)
    args = ap.parse_args()

    if args.all:
        failures = 0
        for arch in ARCH_NAMES:
            for shape_name in SHAPES:
                r = run_cell(arch, shape_name, args.multi_pod,
                             args.report_dir)
                failures += r["status"] == "failed"
        raise SystemExit(1 if failures else 0)
    assert args.arch and args.shape, "--arch/--shape or --all"
    r = run_cell(args.arch, args.shape, args.multi_pod, args.report_dir)
    raise SystemExit(0 if r["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
