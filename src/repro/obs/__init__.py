"""``repro.obs`` — the observability subsystem.

Three layers over the cycle-level engine, each answering a question the
end-of-run aggregates cannot:

* **windowed telemetry** (:class:`Timeseries`, ``repro.obs.schema``) —
  what was the machine doing *over time*?  The ``telemetry_windows``
  Spec knob makes the engine accumulate a ``(n_windows, k)`` in-scan
  timeseries (sleeping/active/backoff core counts, queue depths,
  grant/fail/sleep/wake outcomes, NoC traffic) on both the XLA scan and
  the fused Pallas backends; ``Result.timeseries()`` returns the typed
  view.
* **event traces** (:class:`EventLog`, :mod:`repro.obs.perfetto`) —
  what did core 17 do at cycle 1402?  ``record_trace=True`` runs carry
  per-cycle state and queue-depth traces; ``Result.events()`` gives the
  span/completion view and :func:`perfetto.export` writes a Chrome
  trace JSON loadable at https://ui.perfetto.dev.
* **runner instrumentation** (:class:`RunReport`, :func:`collect`) —
  where did the sweep's wall time go?  Per-chunk compile vs execute
  timing, backend/device facts, persistent-cache hits, and the totals
  of the host spans (:func:`span`) that also mark a profiler trace;
  ambient collection via ``with obs.collect() as report:``.

Submodules import lazily (PEP 562), so the engine's dependency on
``repro.obs.schema`` stays one light leaf module.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

__all__ = ["schema", "Timeseries", "EventLog", "Span", "RunReport",
           "ChunkRecord", "collect", "current", "span", "perfetto"]

if TYPE_CHECKING:                     # pragma: no cover - typing only
    from repro.obs import perfetto, schema
    from repro.obs.events import EventLog, Span
    from repro.obs.runreport import (ChunkRecord, RunReport, collect,
                                     current, span)
    from repro.obs.timeseries import Timeseries

#: attribute -> (submodule, member or None for the module itself)
_LAZY = {
    "schema": ("repro.obs.schema", None),
    "perfetto": ("repro.obs.perfetto", None),
    "Timeseries": ("repro.obs.timeseries", "Timeseries"),
    "EventLog": ("repro.obs.events", "EventLog"),
    "Span": ("repro.obs.events", "Span"),
    "RunReport": ("repro.obs.runreport", "RunReport"),
    "ChunkRecord": ("repro.obs.runreport", "ChunkRecord"),
    "collect": ("repro.obs.runreport", "collect"),
    "current": ("repro.obs.runreport", "current"),
    "span": ("repro.obs.runreport", "span"),
}


def __getattr__(name: str):
    try:
        modname, member = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module 'repro.obs' has no attribute "
                             f"{name!r}") from None
    import importlib
    mod = importlib.import_module(modname)
    value = mod if member is None else getattr(mod, member)
    globals()[name] = value           # cache for subsequent lookups
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
