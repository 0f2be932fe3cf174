"""Smoke test of the simulator's main path on a TPU.

Drives ``repro.sync.run`` / ``Study`` with the default ``auto`` backend,
which resolves to the compiled ``engine_step`` Pallas kernel
(``pallas_tpu``) on a TPU, and checks every phase against the same specs
run on the same chip with the XLA scan backend (``xla_cpu``, the plain
reference): every result field must agree bit for bit, every point must
be ``Result.ok`` and every workload's ``Result.check()`` invariants must
hold.

    python chip_smoke.py             # one chip, the three phases below
    python chip_smoke.py --chips 4   # the workloads Study sharded over 4
                                     # chips vs unsharded runs on chip 0

Phases on one chip:

* ``fig3``: ``colibri`` and ``lrsc`` on the Fig. 3 histogram, 256 cores,
  20k cycles, 1 and 1024 bins (one bank tile and four);
* ``cluster2``: ``colibri_hier`` on the ``cluster2`` topology, 1024
  cores, 12k cycles;
* ``workloads``: the ``benchmarks/bench_workloads.py`` Study (5 workloads
  x 5 protocols x 2 seeds) at 256 cores.

Each phase prints its resolved backend, compile and execute seconds and
simulated core-cycles/s, labelled with the device kind.  These timings
are a first look, not a benchmark.  The last line of standard output is
one JSON object naming the device.  The script exits non-zero, printing
no result, when JAX finds no TPU or any check fails.  It runs in one
process and starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

WORKLOADS = ("rmw_loop", "ms_queue", "treiber_stack", "zipf_histogram",
             "barrier_phases")
PROTOCOLS = ("colibri", "lrscwait", "mwait_lock", "lrsc", "amo_lock")

#: the backend ``auto`` must resolve to on every phase
KERNEL_BACKEND = "pallas_tpu"
#: phase sizes (the paper's 256-core platform, the 1024-core cluster2)
FIG3 = dict(n_cores=256, cycles=20_000)
CLUSTER2 = dict(n_cores=1024, cycles=12_000)
GRID = dict(n_cores=256, cycles=6_000)


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def _fields_differ(a, b):
    """Result fields whose values are not identical in ``a`` and ``b``."""
    if set(a.stats) != set(b.stats):
        return sorted(set(a.stats) ^ set(b.stats))
    bad = []
    for k in sorted(a.stats):
        x, y = np.asarray(a.stats[k]), np.asarray(b.stats[k])
        nan = x.dtype.kind == "f" and y.dtype.kind == "f"
        if x.shape != y.shape or not np.array_equal(x, y, equal_nan=nan):
            bad.append(k)
    return bad


def _verify(label, got, ref):
    """``got`` must be ok, agree with ``ref`` in every field, and pass
    its workload's invariants."""
    for r in (got, ref):
        if not r.ok:
            raise SmokeFailure(f"{label}: error record: {r.error}")
    bad = _fields_differ(got, ref)
    if bad:
        raise SmokeFailure(f"{label}: fields differ from the reference: "
                           f"{bad}")
    got.check()


def _core_cycles(specs):
    return sum(s.topology.n_cores * s.costs.cycles for s in specs)


def _single(label, spec, kind):
    """One spec through ``repro.sync.run``: the kernel path twice (the
    first call compiles, the second reuses the executable) and the scan
    reference once."""
    from repro.core.sim import resolve_backend
    from repro.sync import run
    backend = resolve_backend(spec.to_params().backend)
    if backend != KERNEL_BACKEND:
        raise SmokeFailure(f"{label}: auto resolved to {backend}")
    t0 = time.perf_counter()
    first = run(spec)
    t1 = time.perf_counter()
    got = run(spec)
    t2 = time.perf_counter()
    ref = run(spec.replace(backend="xla_cpu"))
    t3 = time.perf_counter()
    _verify(label, got, ref)
    if _fields_differ(first, got):
        raise SmokeFailure(f"{label}: two runs of one spec differ")
    execute_s = t2 - t1
    print(f"{label}: device={kind} backend={backend} "
          f"compile_s={(t1 - t0) - execute_s:.3f} execute_s={execute_s:.3f} "
          f"core_cycles_per_s={_core_cycles([spec]) / execute_s:.4g} "
          f"scan_reference_wall_s={t3 - t2:.3f} "
          f"ops={got.ops_total} polls={got.polls} msgs={got.msgs}",
          flush=True)


def _study(label, kind, specs, backend=None):
    """``specs`` as one Study, run twice: the first run compiles, the
    second reuses the executables.  Prints the phase line; returns the
    second run's results and the first run's RunReport."""
    from repro import obs
    from repro.sync import Study
    if backend is not None:
        specs = [s.replace(backend=backend) for s in specs]
    t0 = time.perf_counter()
    with obs.collect() as report:
        first = Study.from_specs(specs).run()
    t1 = time.perf_counter()
    results = Study.from_specs(specs).run()
    execute_s = time.perf_counter() - t1
    for s, a, b in zip(specs, first, results):
        if _fields_differ(a, b):
            raise SmokeFailure(f"{label} {_label(s)}: two runs differ")
    print(f"{label}: device={kind} backend={report.backend} "
          f"devices={sorted({c.devices for c in report.chunks})} "
          f"points={report.n_points} chunks={report.n_chunks} "
          f"compiles={report.n_compiles} "
          f"persistent_cache_hits={report.persistent_cache_hits} "
          f"compile_s={(t1 - t0) - execute_s:.3f} "
          f"execute_s={execute_s:.3f} "
          f"core_cycles_per_s={_core_cycles(specs) / execute_s:.4g}",
          flush=True)
    return results, report


def _workload_specs():
    from repro.sync import Spec, scenario
    # bench_workloads.py's grid, at 256 cores instead of its 64
    overrides = {"rmw_loop": dict(n_addrs=16)}
    return [Spec(protocol=proto, workload=wl, seed=seed, **GRID,
                 **{**scenario(wl), **overrides.get(wl, {})})
            for wl in WORKLOADS for proto in PROTOCOLS for seed in (0, 1)]


def _label(s):
    return (f"{s.protocol.name}/{s.workload.name}/seed{s.costs.seed}"
            f"/{s.topology.n_cores}c")


def one_chip(kind):
    from repro.sync import Spec
    for proto in ("colibri", "lrsc"):
        for bins in (1, 1024):
            _single(f"fig3 {proto} bins={bins}",
                    Spec(protocol=proto, workload="zipf_histogram",
                         zipf_skew=0, n_addrs=bins, **FIG3), kind)
    _single("cluster2 colibri_hier",
            Spec(protocol="colibri_hier", topology="cluster2", clusters=4,
                 n_addrs=4, **CLUSTER2), kind)
    specs = _workload_specs()
    got, rep = _study("workloads", kind, specs)
    if rep.backend != KERNEL_BACKEND:
        raise SmokeFailure(f"workloads: auto resolved to {rep.backend}")
    ref, _ = _study("workloads scan reference", kind, specs,
                    backend="xla_cpu")
    for s, g, r in zip(specs, got, ref):
        _verify(f"workloads {_label(s)}", g, r)


def four_chips(kind):
    import jax
    from repro.sync import run
    specs = _workload_specs()
    got, rep = _study("sharded workloads", kind, specs)
    if rep.backend != KERNEL_BACKEND:
        raise SmokeFailure(f"sharded workloads: auto resolved to "
                           f"{rep.backend}")
    spans = sorted({c.devices for c in rep.chunks})
    if spans != [4]:
        raise SmokeFailure(f"sharded workloads: chunk batches span "
                           f"{spans} devices, expected 4")
    t0 = time.perf_counter()
    with jax.default_device(jax.devices()[0]):
        for s, g in zip(specs, got):
            _verify(f"sharded workloads {_label(s)}", g, run(s))
    print(f"unsharded reference on device 0: {len(specs)} runs, "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the workloads Study sharded over four "
                         "chips, against unsharded runs on chip 0")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (JAX platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"TPU devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    kind = devs[0].device_kind

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.sync import enable_persistent_cache
    print(f"device: {kind} x{len(devs)}; compile cache "
          f"{enable_persistent_cache()}", flush=True)
    try:
        (four_chips if args.chips == 4 else one_chip)(kind)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
