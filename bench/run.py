"""Benchmark of the simulator on the chip: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in one process.  Set-up (from process start, before JAX is
imported, to the window) builds the cell's Specs from its data files and
drives its calls once, compiling or loading every program from the
persistent compile cache.  The window then repeats the cell's public
entry on the same inputs until ``--seconds`` have passed, counting the
call that crosses the end whole:

* ``single`` cells call ``repro.sync.run(spec)``, each call ending in
  its Result on the host;
* ``study`` cells call ``repro.sync.Study.from_specs(points).stream()``
  and consume every point.

With ``--trace 1`` the window runs untraced as usual, then passes run
under the JAX profiler until the first point that lands after
``TRACE_SECONDS`` (in a single-run cell a point is a whole run), and the
per-layer metrics are read from that trace and from the window's
``obs.RunReport``.  After the window closes, everything the
window (and the traced segment) returned is checked against the plain
reference (``reference.py``) and against the window's first pass
(``check.py``).  The last line of standard output is one JSON object;
the compared numbers and their limits are also the last lines of
standard error.  Without a TPU, or with another number of chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import check  # noqa: E402

#: the trace stops when the first point lands after this many seconds
TRACE_SECONDS = 1.0


class NoDevice(Exception):
    """JAX finds no TPU, or another number of chips than the cell asks."""


def log(*a):
    print(*a, flush=True)


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed directory in
    the checkout (the path is part of every cache key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def require_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU visible (JAX platform {devs[0].platform!r})")
    if len(devs) != chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")


class Compiles:
    """Backend compiles and persistent-cache hits, with their times."""

    def __init__(self, jax):
        self.compiles, self.hits = [], []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.perf_counter())

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())

    def count(self, lo, hi):
        return (sum(lo <= t < hi for t in self.compiles),
                sum(lo <= t < hi for t in self.hits))


def _load(rel: str, as_name: str = None):
    """A module of this directory by file (``trace`` would otherwise
    be Python's own)."""
    spec = importlib.util.spec_from_file_location(
        as_name or "bench_" + rel.replace("/", "_").replace(".", "_"),
        os.path.join(BENCH, rel + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The per-layer metric ``name``'s reader, ``metrics/<name>.py``."""
    return _load("metrics/" + name).read


def reference_stats(c, fields, broken=None):
    """The reference's statistics for every point of a pass, or the
    control's with ``broken``."""
    import reference
    single = c["traffic"]["mode"] == "single"
    energy = c["config"]["energy_model"]
    raw = reference.run_points(
        [cells.reference_point(f, single) for f in fields], broken=broken)
    return [reference.derive(r, f["cycles"], energy)
            for r, f in zip(raw, fields)]


def p95(values):
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[max(math.ceil(0.95 * len(s)), 1) - 1]


class Entry:
    """The cell's public entry on its inputs, one pass per call."""

    def __init__(self, sync, specs, mode, annotate):
        self.sync, self.specs, self.mode = sync, specs, mode
        self.index = {s: i for i, s in enumerate(specs)}
        self.annotate = annotate
        from repro.obs.runreport import RunReport
        self.report = RunReport()
        self.dups = 0
        self.latencies = []
        #: point index of every Result, in the order they landed
        self.landed = []

    def one(self, deadline=None, stop=None):
        """One pass.  Returns the Results in point order (None where a
        point never came).  ``stop`` is called once, as soon as a point
        has landed at or after ``deadline``."""
        out = [None] * len(self.specs)
        t_sub = time.perf_counter()

        def land(i, r):
            nonlocal stop
            now = time.perf_counter()
            self.latencies.append(now - t_sub)
            self.landed.append(i)
            if out[i] is not None:
                self.dups += 1
            out[i] = r
            if stop is not None and now >= deadline:
                stop()
                stop = None

        if self.mode == "single":
            for i, s in enumerate(self.specs):
                with self.annotate("bench.sync_run"):
                    r = self.sync.run(s)
                land(i, r)
            return out
        it = self.sync.Study.from_specs(self.specs).stream(
            report=self.report)
        while True:
            with self.annotate("bench.study_next"):
                r = next(it, None)
            if r is None:
                return out
            land(self.index[r.spec], r)


def run_cell(c, seed: int, seconds: float, trace: bool, t0: float = T0,
             require=require_devices) -> dict:
    """Set-up, window, optional traced segment and checks of one run of
    cell ``c`` (as :func:`cells.cell` returns it).  Returns the result
    object; raises :class:`NoDevice` before any work without the chips."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    require(jax, c["chips"])
    devs = jax.devices()
    kind = devs[0].device_kind
    counter = Compiles(jax)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro.sync as sync
    from repro.core.sim import resolve_backend

    mode = c["traffic"]["mode"]
    fields = cells.point_fields(c, seed)
    specs = [sync.Spec(**f) for f in fields]
    annotate = jax.profiler.TraceAnnotation
    drv = Entry(sync, specs, mode, annotate)
    log(f"cell {c['name']}: device={kind} x{len(devs)} mode={mode} "
        f"points={len(specs)} backend="
        f"{resolve_backend(specs[0].to_params().backend)} "
        f"compile_cache={cache_dir()}")

    # ---- set-up: every shape this cell uses, once ----------------------
    drv.one()
    setup_s = time.perf_counter() - t0
    drv.latencies.clear()
    drv.report.chunks.clear()

    # ---- the window -----------------------------------------------------
    passes = []
    w0 = time.perf_counter()
    while True:
        passes.append(drv.one())
        if time.perf_counter() - w0 >= seconds:
            break
    w1 = time.perf_counter()
    window_s = w1 - w0
    n_win = len(passes)
    latencies = list(drv.latencies)
    w_compiles, w_hits = counter.count(w0, w1)
    dispatch_s = sum(ch.compile_s for ch in drv.report.chunks)
    drain_s = sum(ch.execute_s for ch in drv.report.chunks)
    points = n_win * len(specs)
    core_cycles = n_win * sum(s.topology.n_cores * s.costs.cycles
                              for s in specs)
    log(f"window: passes={n_win} points={points} window_s={window_s!r} "
        f"compiles_in_window={w_compiles} cache_hits_in_window={w_hits} "
        f"cache_hits_in_setup={counter.count(0, w0)[1]} "
        f"chunks={len(drv.report.chunks)} dispatch_s={dispatch_s!r} "
        f"drain_s={drain_s!r}")

    e2e = {"setup_s": setup_s}
    if mode == "single":
        e2e["core_cycles_per_s"] = core_cycles / window_s
    else:
        e2e["points_per_s"] = points / window_s
        e2e["point_p95_s"] = p95(latencies)
    log("end_to_end: " + " ".join(f"{k}={v!r}" for k, v in e2e.items()))

    # ---- traced segment -------------------------------------------------
    # passes under the profiler until the first point that lands after
    # TRACE_SECONDS; a single run returns its Result before the next is
    # dispatched, so the traced runs' device work lies inside the marks
    # and their cycles are the cycles the trace holds
    reduced = traced_cycles = None
    if trace:
        tr = _load("trace")
        tdir = os.path.join(ROOT, ".bench_trace", c["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        n0, stopped = len(drv.landed), []

        def stop():
            with annotate("bench.trace_stop"):
                pass
            jax.profiler.stop_trace()
            stopped.append(len(drv.landed))

        jax.profiler.start_trace(tdir, profiler_options=opts)
        with annotate("bench.trace_start"):
            pass
        deadline = time.perf_counter() + TRACE_SECONDS
        while not stopped:
            passes.append(drv.one(deadline, stop))
        if mode == "single":
            traced_cycles = sum(specs[i].costs.cycles
                                for i in drv.landed[n0:stopped[0]])
        t_read = time.perf_counter()
        reduced = tr.reduce_dir(tdir)
        log(f"trace: window_s={reduced['window_s']!r} "
            f"busy_s={reduced['busy_s']!r} kernel_s={reduced['kernel_s']!r} "
            f"points={stopped[0] - n0} cycles={traced_cycles} "
            f"kernel_calls={reduced['kernel_calls']!r} "
            f"devices={reduced['n_devices']} "
            f"read_s={time.perf_counter() - t_read!r}")
        shutil.rmtree(tdir, ignore_errors=True)

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    # ---- the reference, once the window has closed ----------------------
    import reference
    t_ref = time.perf_counter()
    ref = reference_stats(c, fields)
    log(f"reference: points={len(ref)} "
        f"reference_s={time.perf_counter() - t_ref!r}")
    verdict = check.judge(passes, ref, dups=drv.dups)
    for note in verdict["notes"]:
        log(f"disagreement: {note}")

    if trace:
        p0 = specs[0].to_params()
        record = dict(mode=mode, window_s=window_s, dispatch_s=dispatch_s,
                      drain_s=drain_s,
                      traced_cycles=traced_cycles,
                      device_kind=kind, protocol=p0.protocol,
                      n_cores=p0.n_cores, n_groups=p0.n_groups,
                      banks=reference.bank_count(p0.n_addrs))
        want = c["metrics"]["per_layer"]
        values = {m["name"]: reader(m["name"])(reduced, record, c)
                  for m in want}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in want if values[m["name"]] is not None}
    else:
        want = c["metrics"]["end_to_end"]
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in want}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    attempted = sum(len(p) for p in passes)
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": verdict["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in verdict["numbers"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = cells.cell(args.workload)
    try:
        out = run_cell(c, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
