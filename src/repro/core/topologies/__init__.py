"""NoC topology plugins for the cycle-level engine.

Importing this package registers every built-in topology:

=============  ==========================================================
``flat``       single crossbar (the engine's historical shape); compiles
               to no tables at all — bit-identical to the pre-topology
               engine on every protocol × workload golden
``cluster2``   two-level hierarchical-cluster NoC, arXiv:2307.10248
               latencies (+8 cyc / bw÷4 cross-cluster)
``cluster3``   three-level variant: cluster (+6 / ÷2) below a top-level
               group boundary (+12 / ÷8)
=============  ==========================================================

A topology compiles ``(p, n_cores, n_addrs)`` into static per-level
core and bank cluster ids
(:class:`~repro.core.topologies.base.TopoTables`) from which the
engine derives each lane's link-crossing mask, hop count and extra
latency, closed over as constants — the scan carry contract is
untouched and mixed-topology sweeps chunk per compile group like any
other static field.

New topologies: subclass :class:`~repro.core.topologies.base.Topology`,
decorate with :func:`~repro.core.topologies.registry.register`, and
import the module here.  Certify with the trace-safety audit
(``python -m repro.analysis trace``) plus the placement property tests
in ``tests/test_topology.py``.
"""
from repro.core.topologies import cluster, flat
from repro.core.topologies.base import LinkLevel, TopoTables, Topology
from repro.core.topologies.registry import get, names, register

__all__ = ["LinkLevel", "TopoTables", "Topology", "get", "names",
           "register", "cluster", "flat"]
