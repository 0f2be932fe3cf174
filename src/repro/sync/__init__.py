"""``repro.sync`` — the stable, typed, declarative public API of the
LRSCwait/Colibri reproduction.

Everything a benchmark, figure script, or downstream user needs lives
here; the engine underneath (``repro.core.sim`` / ``repro.core.sweep``)
is an implementation detail whose legacy entry points now emit
``DeprecationWarning``.

Three nouns:

* :class:`Spec` — a frozen, validated description of one simulation
  point (protocol / workload / topology / costs sub-groups; built from
  kwargs, dicts, or JSON; bad names and impossible values raise at
  construction with the registries' available names).
* :class:`Result` — the typed result of one point: named accessors for
  the paper's metric triple (``throughput`` / ``jain_fairness`` /
  ``energy_pj_per_op``) and latency percentiles, raw counters under
  ``.stats``, shared row/JSON serialization (``to_row`` / ``to_json``).
* :class:`Study` — a declarative multi-point experiment
  (``Study(base).grid(lat=[1, 4, 16]).zip(seed=range(4))``) compiled
  onto the fingerprint-grouped vmapped sweep runner, with batch
  (:meth:`Study.run`) and streaming (:meth:`Study.stream`) execution.

Quickstart::

    from repro.sync import Spec, Study, run

    r = run(Spec(protocol="colibri", workload="ms_queue",
                 n_cores=64, n_addrs=2))
    print(r.throughput, r.jain_fairness, r.energy_pj_per_op, r.polls)

    study = Study(Spec(workload="zipf_histogram", n_addrs=64)) \\
        .grid(protocol=["colibri", "lrsc"], zipf_skew=[0, 100, 200])
    for res in study.stream():
        print(res.spec.protocol.name, res.to_row())

Results are **bit-identical** to the legacy ``sim.run`` /
``sweep.sweep`` surface (same engine, same derivation layer) —
``tests/test_sync_api.py`` locks that in across the full
protocol × workload grid.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

from repro.core import protocols as _protocols
from repro.core import workloads as _workloads
from repro.core import sim as _sim
from repro.core.metrics import METRIC_TRIPLE
from repro.core.sweep import enable_persistent_cache
from repro.obs.runreport import span
from repro.sync.result import Result
from repro.sync.spec import Costs, Protocol, Spec, Topology, Workload
from repro.sync.study import Study

__all__ = ["Spec", "Result", "Study", "run",
           "Protocol", "Workload", "Topology", "Costs",
           "protocols", "workloads", "scenario",
           "METRIC_TRIPLE", "enable_persistent_cache"]


def run(spec: Optional[Spec] = None, *, energy_fit=None,
        **flat: Any) -> Result:
    """Run ONE simulation point and return its typed :class:`Result`.

    Accepts a :class:`Spec` (or spec dict), or flat Spec fields
    directly: ``run(protocol="colibri", n_addrs=1)``.  ``energy_fit``
    overrides the frozen Table II calibration behind
    ``energy_pj_per_op``.
    """
    with span("repro.run"):
        if spec is None:
            spec = Spec(**flat)
        else:
            if isinstance(spec, dict):
                spec = Spec.from_dict(spec)
            if flat:
                spec = spec.replace(**flat)
        return Result(spec=spec,
                      stats=_sim.execute(spec.to_params(),
                                         energy_fit=energy_fit))


def protocols() -> Tuple[str, ...]:
    """Names of every registered synchronization protocol."""
    return _protocols.names()


def workloads() -> Tuple[str, ...]:
    """Names of every registered concurrent-algorithm workload."""
    return _workloads.names()


def scenario(workload: str) -> dict:
    """A workload's canonical Spec overrides (hot-word count, modify
    time, skew, ...) — merge into a :class:`Spec` instead of re-stating
    workload parameters per figure:
    ``Spec(workload="ms_queue", **scenario("ms_queue"))``."""
    return dict(_workloads.get(workload).scenario)
