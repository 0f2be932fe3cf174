"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus a headline summary that
EXPERIMENTS.md quotes.

``--list`` prints the available benchmark names; ``--only <name>`` runs
one benchmark (an exact name match wins, otherwise substring match);
``--out DIR`` redirects the JSON report (default: ``reports/``);
``--profile <name>`` runs one benchmark inside ``jax.profiler.trace()``
and prints the dump directory (open it with TensorBoard's profile
plugin or https://ui.perfetto.dev)::

    PYTHONPATH=src:benchmarks/.. python benchmarks/run.py --list
    PYTHONPATH=src:benchmarks/.. python benchmarks/run.py --only engine
    PYTHONPATH=src:benchmarks/.. python benchmarks/run.py --out /tmp/r
    PYTHONPATH=src:benchmarks/.. python benchmarks/run.py --profile engine

Every report carries a top-level ``provenance`` block (git sha, jax
versions, device, backend, timestamp — ``benchmarks/_common.provenance``)
and a per-benchmark ``run_report`` with the sweep runner's per-chunk
compile/execute instrumentation (``repro.obs.RunReport``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time


def _run(name, mod):
    from repro import obs
    t0 = time.perf_counter()
    with obs.collect() as report:
        rs = mod.rows()
    dt_us = (time.perf_counter() - t0) * 1e6 / max(len(rs), 1)
    head = mod.headline(rs)
    derived = ";".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in head.items())
    print(f"{name},{dt_us:.1f},{derived}")
    if report.n_chunks:
        print(f"#   sweep: {report.summary()}")
    return {"rows": rs, "headline": head, "run_report": report.to_dict()}


def _select(benches, name):
    """The benchmark subset a --only/--profile NAME selects."""
    if name in benches:                   # exact name wins: "summary"
        return {name: benches[name]}
    sel = {k: v for k, v in benches.items() if name in k}
    if not sel:
        raise SystemExit(f"{name!r} matches none of: " + ", ".join(benches))
    return sel


def main(argv=None) -> None:
    from repro.sync import enable_persistent_cache
    enable_persistent_cache()        # repeat runs skip XLA recompiles
    from benchmarks import (bench_area, bench_energy, bench_engine,
                            bench_faults, bench_histogram,
                            bench_interference, bench_locks, bench_queue,
                            bench_scatter_kernel, bench_sweep,
                            bench_topology, bench_workloads, fig_summary)
    benches = {
        "summary": fig_summary,
        "fig3_histogram": bench_histogram,
        "fig4_locks": bench_locks,
        "fig5_interference": bench_interference,
        "fig6_queue": bench_queue,
        "table1_area": bench_area,
        "table2_energy": bench_energy,
        "scatter_kernel": bench_scatter_kernel,
        "sweep_speedup": bench_sweep,
        "workloads_grid": bench_workloads,
        "engine": bench_engine,
        "faults": bench_faults,
        "topology": bench_topology,
    }
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", metavar="NAME", default=None,
                    help="run a single benchmark (exact name first, then "
                         "substring match against " + ", ".join(benches)
                         + ")")
    ap.add_argument("--list", action="store_true",
                    help="print the available benchmark names and exit")
    ap.add_argument("--out", metavar="DIR", default=None,
                    help="directory for the JSON report "
                         "(default: <repo>/reports)")
    ap.add_argument("--profile", metavar="NAME", default=None,
                    help="run ONE benchmark under jax.profiler.trace() "
                         "and print the dump directory (selects like "
                         "--only; must match exactly one benchmark)")
    args = ap.parse_args(argv)
    if args.list:
        for name in benches:
            print(name)
        return
    profile_dir = None
    if args.profile:
        selected = _select(benches, args.profile)
        if len(selected) != 1:
            raise SystemExit(f"--profile {args.profile!r} must match "
                             f"exactly one benchmark, got: "
                             + ", ".join(selected))
    elif args.only:
        selected = _select(benches, args.only)
    else:
        selected = benches

    out_dir = args.out or os.path.join(os.path.dirname(__file__), "..",
                                       "reports")
    os.makedirs(out_dir, exist_ok=True)

    from benchmarks._common import provenance
    results = {"provenance": provenance()}
    print("name,us_per_call,derived")
    if args.profile:
        import jax
        name = next(iter(selected))
        profile_dir = os.path.join(out_dir,
                                   f"profile_{name}_{int(time.time())}")
        prof_ctx = jax.profiler.trace(profile_dir)
    else:
        prof_ctx = contextlib.nullcontext()
    with prof_ctx:
        for name, mod in selected.items():
            results[name] = _run(name, mod)
    if profile_dir:
        print(f"# profiler dump -> {profile_dir}")
        print("#   view: tensorboard --logdir <dir>  (profile plugin), or "
              "load the .trace.json.gz at https://ui.perfetto.dev")

    picked = args.only or args.profile
    suffix = f".{picked}" if picked else ""
    out_path = os.path.join(out_dir, f"benchmarks{suffix}.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# full rows -> {out_path}")


if __name__ == "__main__":
    main()
