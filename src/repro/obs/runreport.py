"""``RunReport`` — sweep-runner instrumentation (obs layer 3).

The fingerprint-grouped vmapped sweep (``repro.core.sweep``) dispatches
work in chunks; where its wall time goes — tracing+compiling a new
executable vs executing a cached one, and how the persistent
compilation cache behaves across runs — was previously invisible.  A
:class:`RunReport` records one :class:`ChunkRecord` per dispatched
chunk plus environment facts (backend, device kind, device count,
batch ceiling) and summarizes them for ``benchmarks/run.py`` output and
report JSON.

Timing model (CPU/asynchronous-dispatch reality): the jitted sweep call
traces and compiles **synchronously** on an in-process cache miss, so a
chunk's dispatch wall time is compile time when ``compiled`` is True
and sub-millisecond otherwise; execution drains at the chunk's
``jax.device_get``, so materialize wall time is execute time.  The
records name them ``compile_s`` / ``execute_s`` accordingly.

Usage — ambient (how ``benchmarks/run.py`` instruments every study a
benchmark runs, without threading a parameter through 11 modules)::

    from repro import obs
    with obs.collect() as report:
        study.run()
    print(report.summary())

or explicit: ``Study.run(report=report)`` / ``Study.stream(report=...)``
/ ``sweep_iter(..., report=report)``.

Persistent-cache hits are counted through ``jax.monitoring`` events.

Host spans: :func:`span` names a stretch of host work.  Each opens a
``jax.profiler.TraceAnnotation`` and, while a report is collecting,
adds its wall time and a count to ``RunReport.spans``.  ``repro.sync.run``
enters ``repro.run`` (the whole call) and, inside it,
``repro.run.dispatch`` (the jitted call: argument hashing, cache lookup,
enqueue, compile on a miss), ``repro.run.fetch`` (waiting for the device
and the transfer) and ``repro.run.metrics`` (metric derivation).  The
sweep enters ``repro.sweep.dispatch``, ``repro.sweep.drain`` and
``repro.sweep.metrics`` (per point) with ``chunk=<index>``, and
``repro.sweep.isolate`` when a chunk fails.  On the device, each stage
of the engine's scan body runs under a ``jax.named_scope`` (``sim.issue``
... ``sim.faults``, see ``repro.core.sim.simulate``).  To see both, run
under the profiler and open the trace (TensorBoard or Perfetto)::

    import jax
    with jax.profiler.trace("/tmp/prof"):
        repro.sync.run(spec)

The host spans are the ``repro.*`` events of the host plane; the device
ops carry their stage in the compiled program's ``op_name`` metadata
(``bench/stages.py`` maps traced ops to stages).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: the active ambient report (see :func:`collect` / :func:`current`)
_current: Optional["RunReport"] = None

_listener_installed = False


def _install_cache_listener() -> None:
    """Count persistent-compilation-cache hits into the active report.

    ``jax.monitoring`` fires a cache-hit event when an executable is
    deserialized from the on-disk cache instead of compiled.  One
    process-wide listener routes the events to whichever report is
    currently collecting.
    """
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    from jax import monitoring

    def _on_event(event: str, **kw: Any) -> None:
        rep = _current
        if rep is not None and "cache_hit" in event:
            rep.persistent_cache_hits += 1

    monitoring.register_event_listener(_on_event)


@dataclasses.dataclass
class ChunkRecord:
    """One dispatched sweep chunk."""
    label: str            # fingerprint summary (protocol/workload/shape)
    points: int           # real configuration points in the chunk
    batch: int            # padded batch actually dispatched
    compile_s: float      # dispatch wall: trace+compile on a miss, ~0 on hit
    execute_s: float      # materialize wall: device_get drain
    compiled: bool        # this dispatch built a new in-process executable
    devices: int = 1      # devices the chunk's result batch is sharded over
    topo: str = "none"    # topology lookup traced: none | select | gather
    banks: int = 0        # bank count of the traced program
    bank_tiles: int = 0   # kernel grid, a // block_a; 0: no kernel ran
    to_cores: str = "scatter"  # bank-to-core delivery: scatter | gather
    q_cap: int = 0        # queue slots per bank row; 0: no queue
    bank_last: int = 0    # 1: bank state stored with banks on the last axis

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RunReport:
    """Instrumentation record of one (or more) sweep executions."""
    backend: str = ""               # resolved engine backend of the run
    device: str = ""                # jax device kind (e.g. "cpu", "TPU v4")
    n_devices: int = 0
    max_batch: Optional[int] = None
    chunks: List[ChunkRecord] = dataclasses.field(default_factory=list)
    persistent_cache_hits: int = 0
    #: host span name -> [total seconds, count] (see :func:`span`)
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)

    # ---- recording (called by repro.core.sweep) -------------------------
    def note_env(self, backend: str, max_batch: int) -> None:
        """Fill environment facts once per sweep invocation."""
        self.backend = backend
        self.max_batch = max_batch
        try:
            import jax
            devs = jax.devices()
            self.device = devs[0].device_kind if devs else ""
            self.n_devices = len(devs)
        except Exception:           # pragma: no cover - env probing only
            pass

    def record_chunk(self, label: str, points: int, batch: int,
                     compile_s: float, execute_s: float,
                     compiled: bool, devices: int = 1,
                     topo: str = "none", banks: int = 0,
                     bank_tiles: int = 0,
                     to_cores: str = "scatter", q_cap: int = 0,
                     bank_last: int = 0) -> None:
        self.chunks.append(ChunkRecord(label=label, points=points,
                                       batch=batch, compile_s=compile_s,
                                       execute_s=execute_s,
                                       compiled=compiled, devices=devices,
                                       topo=topo, banks=banks,
                                       bank_tiles=bank_tiles,
                                       to_cores=to_cores, q_cap=q_cap,
                                       bank_last=bank_last))

    # ---- aggregates -----------------------------------------------------
    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    @property
    def n_points(self) -> int:
        return sum(c.points for c in self.chunks)

    @property
    def n_compiles(self) -> int:
        return sum(c.compiled for c in self.chunks)

    @property
    def compile_s(self) -> float:
        return sum(c.compile_s for c in self.chunks)

    @property
    def execute_s(self) -> float:
        return sum(c.execute_s for c in self.chunks)

    # ---- presentation ---------------------------------------------------
    def summary(self) -> str:
        """One human line: where the sweep wall time went."""
        return (f"{self.n_points} pts / {self.n_chunks} chunks on "
                f"{self.backend or '?'} ({self.n_devices}x"
                f"{self.device or '?'}): compile {self.compile_s:.2f}s "
                f"({self.n_compiles} new), execute {self.execute_s:.2f}s, "
                f"persistent-cache hits {self.persistent_cache_hits}")

    def to_dict(self) -> Dict[str, Any]:
        """JSON-ready dict (what ``benchmarks/run.py`` embeds per
        benchmark under ``run_report``)."""
        return {"backend": self.backend, "device": self.device,
                "n_devices": self.n_devices, "max_batch": self.max_batch,
                "n_points": self.n_points, "n_chunks": self.n_chunks,
                "n_compiles": self.n_compiles,
                "compile_s": self.compile_s, "execute_s": self.execute_s,
                "persistent_cache_hits": self.persistent_cache_hits,
                "chunks": [c.to_dict() for c in self.chunks],
                "spans": {k: list(v) for k, v in self.spans.items()}}


def current() -> Optional[RunReport]:
    """The ambient report sweeps record into, or None."""
    return _current


@contextlib.contextmanager
def collect(report: Optional[RunReport] = None):
    """Collect sweep instrumentation for everything run in this block.

    Yields the active :class:`RunReport`; nests (the previous ambient
    report is restored on exit).
    """
    global _current
    _install_cache_listener()
    rep = report if report is not None else RunReport()
    prev = _current
    _current = rep
    try:
        yield rep
    finally:
        _current = prev


@dataclasses.dataclass
class SpanTime:
    """Wall seconds of one :func:`span`, set when the span closes."""
    seconds: float = 0.0


@contextlib.contextmanager
def span(name: str, report: Optional[RunReport] = None, **args: Any):
    """Name a stretch of host work: a ``jax.profiler.TraceAnnotation``
    (``args`` become its arguments, e.g. ``chunk=3``), plus its wall time
    and a count in ``report.spans`` (``report`` defaults to the ambient
    one; with neither, nothing is recorded).  Yields a :class:`SpanTime`
    whose ``seconds`` is set on exit, exceptions included."""
    rep = report if report is not None else _current
    took = SpanTime()
    with TraceAnnotation(name, **args):
        t0 = time.perf_counter()
        try:
            yield took
        finally:
            took.seconds = time.perf_counter() - t0
            if rep is not None:
                tot = rep.spans.setdefault(name, [0.0, 0])
                tot[0] += took.seconds
                tot[1] += 1
