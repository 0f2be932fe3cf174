"""Batched parameter sweeps: jit the engine once, ``vmap`` the grid.

The benchmark figures each run dozens of configurations.  The engine
jits per *static* parameter set, so a sweep over
``(seed, n_addrs, lat, work, ...)`` used to pay one full XLA compile per
point.  This runner groups configurations by their static fingerprint
(protocol, workload program, core count, cycle count, queue capacity,
group count, trace flag, unroll factor), lifts
every other scalar into a traced axis (``sim.DYN_FIELDS``), and runs each
group through a single ``jax.vmap``-ed compilation of the engine.

Entry points: :func:`sweep_params` (list in, input-order list out) and
:func:`sweep_iter` (generator yielding points as chunks materialize) —
both internal machinery behind ``repro.sync.Study.run()`` /
``.stream()``; the module-level :func:`sweep` / :func:`sweep_grid` are
deprecated legacy shims over them.

Executor shape (the hot path behind every figure):

* **Chunking** — each fingerprint group is split into ``max_batch``-point
  chunks (default 256, ``REPRO_SWEEP_MAX_BATCH``), so a 4096-point grid
  never materializes 4096 copies of the engine state at once; chunks of
  equal length reuse one compilation.
* **Overlapped dispatch** — chunks are dispatched ahead of
  materialization through a bounded look-ahead window (4 chunks in
  flight): jax computations are asynchronous, so the next chunks'
  host-side setup and device work overlap the current chunk's
  execution instead of blocking per group, while the window bounds how
  many chunk outputs are resident at once.
* **One transfer per chunk** — results come back through a single
  ``jax.device_get`` of the whole result pytree per chunk (the former
  per-key ``np.asarray`` did one host sync per array).
* **Device sharding** — with more than one device visible, chunks are
  padded to a multiple of ``jax.device_count()`` and their batch axis is
  sharded across devices (``NamedSharding``); the single-device path is
  byte-for-byte the old behaviour.
* **Persistent compilation cache** — :func:`enable_persistent_cache`
  points jax at an on-disk cache (``$JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``) so repeated benchmark runs skip recompiles
  entirely (``benchmarks/run.py`` and ``chip_smoke.py`` call it at
  startup).

``n_addrs`` is traced too: configs bucket by the next power of two of
their address count (``_bucket_a``), the engine allocates banks for the
bucket and runs the live count through the address hash — so nearby
contention levels share one compile without a hot 1-address point
paying a 256-bank arbitration loop.  Results are **identical** to
per-config ``sim.run`` calls — all engine state is integer, and the
traced scalars feed the same arithmetic the Python constants did
(``tests/test_sweep.py`` locks this in, including chunked and sharded
execution).

EXPERIMENTS.md §Sweep and §Engine-throughput record the measured
speedups; ``benchmarks/bench_sweep.py`` and ``benchmarks/bench_engine.py``
regenerate them.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import os
import warnings
from functools import partial
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.sim import (DYN_FIELDS, _DENSE_BANK_ELTS, SimParams,
                            derive_metrics, dispatch_args, simulate)
from repro.obs.runreport import span

#: fields that must match for configs to share one compilation — the
#: workload's compiled program, the trace shape and the scan unroll
#: factor are baked into the scan body, so all are part of the fingerprint
STATIC_FIELDS = ("protocol", "workload", "n_cores", "cycles", "q_slots",
                 "n_groups", "record_trace", "unroll", "backend",
                 "telemetry_windows", "faults", "topology", "clusters")

#: default ceiling on points per compiled vmap invocation
#: (``REPRO_SWEEP_MAX_BATCH`` overrides — read at each ``sweep()`` call,
#: so setting it after import still takes effect)
DEFAULT_MAX_BATCH = 256


#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed
#: inside the checkout, because the path is part of every cache key
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Enable jax's on-disk compilation cache (idempotent).

    Repeated benchmark runs re-trace the same engine fingerprints; with
    the cache enabled the XLA compile step is skipped on every run after
    the first.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that
    is set (placed from outside, and no other directory is used), else
    :data:`DEFAULT_CACHE_DIR`.  Returns the cache directory.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache even fast/small compiles — the sweep fingerprints are many
    # and individually cheap, but a full benchmark run has dozens
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _bucket_a(n_addrs: int) -> int:
    """Bank-allocation bucket: next power of two ≥ ``n_addrs``.

    Mixed-contention configs used to share one compile padded to the
    group's *maximum* address count — a ``n_addrs=1`` point then dragged
    256 banks of arbitration work through every cycle, and with the
    scatter-free hot path that padding dominates wall time.  Bucketing
    by power of two bounds the waste at 2× while keeping the compile
    count logarithmic in the contention range."""
    return 1 << max(n_addrs - 1, 0).bit_length()


def _static_key(p: SimParams):
    return tuple(getattr(p, f) for f in STATIC_FIELDS) + (_bucket_a(p.n_addrs),)


#: headline metrics screened for NaN/inf per point — ``fairness_span``
#: is deliberately absent (inf legitimately encodes a starved core)
_HEADLINE_KEYS = ("throughput", "jain_fairness", "energy_pj_per_op")


def _finite_metrics(res) -> bool:
    for k in _HEADLINE_KEYS:
        v = res.get(k)
        if v is not None and not math.isfinite(float(v)):
            return False
    return True


@partial(jax.jit, static_argnums=(0, 2, 3))
def _sweep_group(rep: SimParams, dyn: Dict[str, jnp.ndarray], batch: int,
                 mesh=None):
    # `batch` sizes the engine's dense-vs-scatter arbitration choice for
    # the vmapped working set; it is already implied by dyn's shapes, so
    # making it static adds no extra compiles
    def run(d):
        return jax.vmap(lambda x: simulate(rep, dyn=x, batch=batch))(d)
    if mesh is None:
        return run(dyn)
    # a sharded batch runs as one shard_map over the mesh: each device
    # simulates its own slice of points.  The compiler cannot partition
    # the engine_step kernel (a Mosaic custom call) by itself, and the
    # kernel's out_shape carries no varying-axes annotation to check.
    spec = PartitionSpec("batch")
    return jax.shard_map(run, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(dyn)


def _batch_sharding():
    """(sharding, n_devices) for the chunk batch axis; (None, 1) on a
    single device — that path is unchanged from the unsharded runner."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None, 1
    mesh = Mesh(np.asarray(devs), ("batch",))
    return NamedSharding(mesh, PartitionSpec("batch")), len(devs)


def sweep_iter(configs: Sequence[SimParams],
               max_batch: Optional[int] = None, energy_fit=None,
               report=None
               ) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
    """Streaming sweep: yield ``(index, result)`` pairs as chunks
    materialize, in chunk-completion order (fingerprint groups in
    first-appearance order, chunks in order within a group) — NOT input
    order.  Each result dict is exactly what :func:`sweep` returns for
    that config, metric triple included; consumers that need input
    order collect into a list by index (that is all :func:`sweep`
    does).  This is the engine behind ``repro.sync.Study.stream()``:
    figure scripts consume points while later chunks are still in
    flight instead of waiting on the full grid.

    ``report`` (a :class:`repro.obs.RunReport`) records per-chunk
    compile/execute wall time, environment facts and the totals of the
    host spans; when None, the ambient report of an enclosing
    ``repro.obs.collect()`` block is used (no-op when neither exists).
    The spans (``repro.sweep.dispatch`` / ``.drain`` / ``.metrics`` /
    ``.isolate``, each with ``chunk=<index>``) also mark a profiler
    trace.  Instrumentation never changes results — it only reads
    clocks around the existing dispatch and transfer points.

    **Failure isolation:** a chunk that raises (at dispatch, execution
    or metric derivation) no longer kills the whole stream.  The
    poisoned chunk is re-run through a bisection ladder — halves
    batched, a failing half split again, a failing single point re-run
    solo — so every healthy point still yields its normal result and
    only the minimal failing set yields a structured error record
    (``{"error": "ExcType: message", "error_stage": ...}``, surfaced as
    ``Result.ok == False``).  Points whose headline metrics come back
    non-finite (NaN/inf throughput, Jain or energy — never the
    legitimately-inf ``fairness_span``) get one solo retry, then an
    error record.  Healthy sweeps take the exact pre-isolation path.
    """
    if max_batch is None:
        max_batch = int(os.environ.get("REPRO_SWEEP_MAX_BATCH",
                                       DEFAULT_MAX_BATCH))
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1 (got {max_batch})")
    if report is None:
        from repro.obs import runreport as _runreport
        report = _runreport.current()            # ambient collect(), or None
    groups: Dict[tuple, List[int]] = {}
    for i, c in enumerate(configs):
        groups.setdefault(_static_key(c), []).append(i)
    sharding, ndev = _batch_sharding()
    pending: List[tuple] = []                    # dispatched, not fetched
    if report is not None and configs:
        from repro.core.sim import resolve_backend
        report.note_env(resolve_backend(configs[0].backend), max_batch)

    def solo(i, stage):
        """Last rung of the isolation ladder: run ONE point un-batched;
        a failure here becomes its structured error record."""
        c = configs[i]
        try:
            rep1 = dataclasses.replace(c, n_addrs=_bucket_a(c.n_addrs))
            dyn1 = {f: jnp.asarray([getattr(c, f)], jnp.int32)
                    for f in DYN_FIELDS}
            out1 = jax.device_get(_sweep_group(rep1, dyn1, 1))
            stage = "metrics"
            m = derive_metrics({k: v[0] for k, v in out1.items()},
                               min(c.n_workers, c.n_cores), c.cycles,
                               energy_fit=energy_fit)
        except Exception as e:       # noqa: BLE001 — fenced by design
            return {"error": f"{type(e).__name__}: {e}",
                    "error_stage": stage}
        if not _finite_metrics(m):
            return {"error": "non-finite headline metrics "
                             "(throughput/jain/energy)",
                    "error_stage": "nonfinite"}
        return m

    def derive_checked(i, res, stage):
        """Per-point metric derivation with the solo-retry fallback."""
        c = configs[i]
        try:
            m = derive_metrics(res, min(c.n_workers, c.n_cores), c.cycles,
                               energy_fit=energy_fit)
        except Exception:            # noqa: BLE001 — fenced by design
            return solo(i, "metrics")
        if not _finite_metrics(m):
            return solo(i, "nonfinite")
        return m

    def isolate(part, stage):
        """Bisected retry of a poisoned chunk: halves re-run batched,
        a failing half recurses, a single point falls through to
        :func:`solo` — healthy points keep their normal results.
        Returns the ``(index, result)`` pairs."""
        if len(part) == 1:
            return [(part[0], solo(part[0], stage))]
        pairs = []
        mid = len(part) // 2
        for half in (part[:mid], part[mid:]):
            chunk = [configs[i] for i in half]
            try:
                rep_h = dataclasses.replace(
                    chunk[0], n_addrs=_bucket_a(chunk[0].n_addrs))
                dyn_h = {f: jnp.asarray([getattr(c, f) for c in chunk],
                                        jnp.int32) for f in DYN_FIELDS}
                out_h = jax.device_get(_sweep_group(rep_h, dyn_h,
                                                    len(chunk)))
            except Exception:        # noqa: BLE001 — fenced by design
                pairs += isolate(half, stage)
                continue
            pairs += [(i, derive_checked(i, {k: v[j] for k, v in
                                             out_h.items()}, stage))
                      for j, i in enumerate(half)]
        return pairs

    def fenced(part, stage, ck):
        # the span closes before the first point is yielded, so it
        # times the ladder and not the consumer
        with span("repro.sweep.isolate", report, chunk=ck):
            pairs = isolate(part, stage)
        yield from pairs

    def materialize(ck, part, out, rec):
        # one device->host transfer per chunk (the whole result pytree)
        try:
            with span("repro.sweep.drain", report, chunk=ck) as took:
                out_np = jax.device_get(out)
        except Exception:            # noqa: BLE001 — fenced by design
            out_np = None
        if rec is not None:
            # async dispatch drains here, so this wall is execute time
            rec.execute_s = took.seconds
        if out_np is None:
            yield from fenced(part, "execute", ck)
            return
        for j, i in enumerate(part):             # padding rows never read
            res = {k: v[j] for k, v in out_np.items()}
            with span("repro.sweep.metrics", report, chunk=ck):
                m = derive_checked(i, res, "metrics")
            yield i, m

    # dispatch chunks ahead of materialization: jax computations are
    # async, so the next chunk's host-side setup (and, with >1 device,
    # its execution) overlaps the previous chunk's run.  The look-ahead
    # window bounds how many chunk outputs are resident on device at
    # once — a record_trace point carries a (cycles, n) trace, so
    # unbounded dispatch would defeat the max_batch memory bound.
    window = 4
    chunk_ids = itertools.count()
    for idxs in groups.values():
        grp = [configs[i] for i in idxs]
        # bank allocation = the group's power-of-two bucket (identical
        # for every member, so every chunk reuses one compilation)
        rep = dataclasses.replace(grp[0],
                                  n_addrs=_bucket_a(grp[0].n_addrs))
        # auto chunk: keep the vmapped dense-arbitration working set
        # (chunk, a, n) inside the engine's cache-friendly budget —
        # measured 2.3× on a 96-point a=16 grid vs one big chunk; grids
        # on the scatter path (large a*n) just take max_batch.
        # ``max_batch`` stays the hard memory ceiling either way.
        an = rep.n_addrs * rep.n_cores
        chunk_cap = max_batch
        if an <= _DENSE_BANK_ELTS:
            chunk_cap = max(1, min(max_batch, _DENSE_BANK_ELTS // an))
        for lo in range(0, len(idxs), chunk_cap):
            ck = next(chunk_ids)
            part = idxs[lo:lo + chunk_cap]
            chunk = [configs[i] for i in part]
            # pad the tail chunk to the full chunk length (and to a
            # device multiple) so it reuses the full chunk's compile
            want = chunk_cap if len(idxs) > chunk_cap else len(chunk)
            want += (-want) % ndev
            padded = chunk + [chunk[-1]] * (want - len(chunk))
            # a worker-free chunk drops the n_workers axis so the engine
            # statically elides the Fig.5 worker machinery (two written
            # (n,) scan carries whose dead writes sit on a compile
            # cliff); chunks with any workers keep the traced axis.  The
            # dropped axis falls back to the representative's static
            # value, so that must be pinned to 0 too — the group leader
            # may carry workers while a later chunk is worker-free.
            drop_workers = not any(c.n_workers for c in padded)
            dyn = {f: jnp.asarray([getattr(c, f) for c in padded], jnp.int32)
                   for f in DYN_FIELDS
                   if f != "n_workers" or not drop_workers}
            crep = dataclasses.replace(rep, n_workers=0) if drop_workers \
                else rep
            if sharding is not None:
                dyn = jax.device_put(dyn, sharding)
            regime = dispatch_args(crep)
            cache_before = _sweep_group._cache_size() \
                if report is not None else 0
            try:
                with span("repro.sweep.dispatch", report, chunk=ck,
                          **regime) as took:
                    out = _sweep_group(crep, dyn, len(padded),
                                       None if sharding is None
                                       else sharding.mesh)
            except Exception:        # noqa: BLE001 — fenced by design
                # poisoned at trace/compile time: fence it now, the
                # stream keeps flowing
                yield from fenced(part, "dispatch", ck)
                continue
            rec = None
            if report is not None:
                # the jitted call traces+compiles synchronously on an
                # in-process cache miss and returns immediately on a
                # hit, so dispatch wall ~= compile time when the cache
                # grew; execution drains at materialize's device_get
                compiled = _sweep_group._cache_size() > cache_before
                report.record_chunk(
                    label=(f"{crep.protocol}/{crep.workload} "
                           f"{crep.n_cores}c a{crep.n_addrs} "
                           f"{crep.cycles}cyc"),
                    points=len(part), batch=len(padded),
                    compile_s=took.seconds, execute_s=0.0,
                    compiled=compiled,
                    devices=len(jax.tree.leaves(out)[0].sharding.device_set),
                    **regime)
                rec = report.chunks[-1]
            pending.append((ck, part, out, rec))
            if len(pending) >= window:
                yield from materialize(*pending.pop(0))
    for chunk_args in pending:
        yield from materialize(*chunk_args)


def sweep_params(configs: Sequence[SimParams],
                 max_batch: Optional[int] = None, energy_fit=None,
                 report=None) -> List[Dict[str, np.ndarray]]:
    """Run every configuration; returns one result dict per config (same
    keys and values as ``sim.execute``), in input order — including the
    paper metric triple (``jain_fairness`` / ``lat_p95`` /
    ``energy_pj_per_op``) attached per point by the shared derivation
    layer (``core.metrics``).  ``energy_fit`` overrides the frozen
    Table II calibration used for ``energy_pj_per_op``.

    Configurations sharing a static fingerprint are batched through one
    vmapped compile in ``max_batch``-point chunks; a heterogeneous list
    degrades gracefully to one compile per fingerprint.  Chunks are
    dispatched up to a 4-chunk look-ahead window before results are
    materialized (one ``device_get`` per chunk), and the batch axis is
    sharded across devices when more than one is visible.

    Internal engine entry point: the supported public surface is
    :class:`repro.sync.Study`, which wraps each point in a typed
    :class:`repro.sync.Result`.
    """
    results: List[Dict[str, np.ndarray]] = [None] * len(configs)  # type: ignore
    for i, res in sweep_iter(configs, max_batch=max_batch,
                             energy_fit=energy_fit, report=report):
        results[i] = res
    return results


def sweep(configs: Sequence[SimParams], max_batch: Optional[int] = None,
          energy_fit=None) -> List[Dict[str, np.ndarray]]:
    """Deprecated legacy entry point — use ``repro.sync.Study``.

    Behaviour is unchanged (bit-identical result dicts, input order;
    locked in by ``tests/test_sync_api.py``); only the warning is new.
    """
    warnings.warn(
        "repro.core.sweep.sweep() is deprecated; use repro.sync.Study "
        "(Study.from_specs(...).run() / .stream()) which returns typed "
        "Results.", DeprecationWarning, stacklevel=2)
    return sweep_params(configs, max_batch=max_batch, energy_fit=energy_fit)


def sweep_grid(base: SimParams, max_batch: Optional[int] = None,
               energy_fit=None, **axes: Sequence
               ) -> List[Dict[str, np.ndarray]]:
    """Deprecated legacy entry point — use
    ``repro.sync.Study(base).grid(...)``.

    Cartesian sweep: ``sweep_grid(base, n_addrs=(1, 16), seed=(0, 1))``
    runs every combination (last axis fastest) and returns results plus
    a ``_config`` entry recording each point's SimParams."""
    warnings.warn(
        "repro.core.sweep.sweep_grid() is deprecated; use "
        "repro.sync.Study(base_spec).grid(...).run() / .stream().",
        DeprecationWarning, stacklevel=2)
    for name in axes:
        if name not in DYN_FIELDS:
            raise ValueError(f"{name!r} is not sweepable; axes: {DYN_FIELDS}")
    points = [base]
    for name, values in axes.items():
        points = [dataclasses.replace(pt, **{name: v})
                  for pt in points for v in values]
    results = sweep_params(points, max_batch=max_batch,
                           energy_fit=energy_fit)
    for pt, res in zip(points, results):
        res["_config"] = pt
    return results
