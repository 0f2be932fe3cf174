"""Topology plugin interface: the machine's NoC shape as data.

The engine (``core.sim``) models a flat core↔bank crossbar; the paper's
Colibri is explicitly hierarchical (per-cluster reservation stations,
cross-cluster handoffs), and the related 1024-core manycore
(arXiv:2307.10248) routes every remote access through a multi-level
cluster NoC with per-level latencies and per-level link bandwidth.  A
:class:`Topology` plugin describes that shape declaratively — a cluster
tree with per-level extra latency and link capacity, plus a placement
rule mapping cores and banks onto clusters — and *compiles* it into
the static factored form the engine's network stage consumes: per
boundary level ``ℓ``, the cluster id of every core (``core_lvl[ℓ]``,
``(n,)``) and of every bank (``bank_lvl[ℓ]``, ``(a,)``), plus the
levels' extra latencies.  Everything per (core, bank) follows from it:

* ``cross[ℓ][c, b]`` — whether a ``c → b`` message crosses level ``ℓ``'s
  boundary, ``core_lvl[ℓ][c] != bank_lvl[ℓ][b]``; crossing messages
  contend for that level's per-cycle link budget (``net_bw // bw_div``)
  on top of the global acceptance budget;
* ``hops[c, b]``   — NoC hop count of a ``c → b`` request (1 for a
  bank in the core's own cluster, +2 per crossed level: up through the
  level router and back down);
* ``extra[c, b]``  — round-trip extra latency in cycles beyond the flat
  ``lat`` baseline, billed once at request issue.

The engine looks up each level's bank-side id of a lane's bank once per
cycle and derives all three per lane; the dense ``(n, a)`` views exist
for the property tests.  The arrays are plain numpy, computed once per
trace and closed over as constants — the engine's ``lax.scan`` carry
contract is untouched, and the ``flat`` topology compiles to the
*absence* of levels (:attr:`TopoTables.is_flat`), so the engine
Python-gates every topology branch off and traces to exactly the
pre-topology jaxpr (the telemetry/faults static-elision discipline,
audited by ``repro.analysis``).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class LinkLevel:
    """One boundary level of the cluster tree, leaf-most first."""
    name: str            # e.g. "cluster", "group"
    extra_lat: int       # round-trip extra cycles for crossing messages
    bw_div: int          # level link budget = max(1, net_bw // bw_div)

    def __post_init__(self):
        if self.extra_lat < 0:
            raise ValueError(f"level {self.name!r}: extra_lat must be >= 0")
        if self.bw_div < 1:
            raise ValueError(f"level {self.name!r}: bw_div must be >= 1")


@dataclasses.dataclass(frozen=True)
class TopoTables:
    """Compiled placement of one (topology, n, a, clusters) point, in
    factored form.  All arrays are numpy (trace-time constants)."""
    core_lvl: Tuple[np.ndarray, ...]      # per level: (n,) int32 cluster id
    bank_lvl: Tuple[np.ndarray, ...]      # per level: (a,) int32 cluster id
    extra_lat: Tuple[int, ...]            # per level: round-trip extra cycles
    core_cluster: np.ndarray              # (n,) int32 leaf-cluster of core
    bank_cluster: np.ndarray              # (a,) int32 leaf-cluster of bank

    @property
    def is_flat(self) -> bool:
        """No levels: the engine gates every topology branch off."""
        return not self.core_lvl

    # ---- dense (n, a) views ---------------------------------------------
    @property
    def cross(self) -> Tuple[np.ndarray, ...]:
        """Per level: (n, a) bool, whether ``c → b`` crosses it."""
        return tuple(cl[:, None] != bl[None, :]
                     for cl, bl in zip(self.core_lvl, self.bank_lvl))

    @property
    def hops(self) -> np.ndarray:
        """(n, a) int32 hop count, 1 + 2 per crossed level."""
        shape = (self.core_cluster.size, self.bank_cluster.size)
        return 1 + 2 * sum(self.cross, np.zeros(shape, np.int32))

    @property
    def extra(self) -> np.ndarray:
        """(n, a) int32 extra latency of the crossed levels."""
        shape = (self.core_cluster.size, self.bank_cluster.size)
        return sum((lat * x for lat, x in zip(self.extra_lat, self.cross)),
                   np.zeros(shape, np.int32)).astype(np.int32)


def cluster_of(idx: np.ndarray, count: int, clusters: int) -> np.ndarray:
    """Block placement: contiguous ``ceil``-free partition of ``count``
    ids into ``clusters`` blocks — ``idx // (count // clusters)`` clamped
    so the remainder joins the last cluster.  Matches the hw_event
    protocol's group geometry (``_geom``) exactly, so the event unit a
    core registers with is the cluster the topology routes it to."""
    c = max(1, min(clusters, count))
    sz = max(1, count // c)
    return np.minimum(idx // sz, c - 1).astype(np.int32)


class Topology:
    """Base topology plugin.  Subclasses declare ``name`` and ``levels``
    and may override the placement hooks."""

    name: str = ""
    #: boundary levels, leaf-most first (empty = flat crossbar)
    levels: Tuple[LinkLevel, ...] = ()

    # ---- placement ------------------------------------------------------
    def core_clusters(self, p, n: int) -> np.ndarray:
        """(n,) leaf-cluster id of every core (block placement)."""
        return cluster_of(np.arange(n), n, getattr(p, "clusters", 1))

    def bank_clusters(self, p, a: int) -> np.ndarray:
        """(a,) leaf-cluster id of every bank.  Banks interleave across
        clusters (``b % clusters``) — the address-interleaved SPM layout
        of the reference manycore, so hot addresses spread over all
        cluster-local memories instead of piling into one."""
        c = max(1, min(getattr(p, "clusters", 1), max(a, 1)))
        return (np.arange(a) % c).astype(np.int32)

    def level_cluster(self, leaf: np.ndarray, level: int, p) -> np.ndarray:
        """Collapse leaf-cluster ids to the cluster id at ``level`` (0 =
        leaf).  Default tree: each level pairs up the clusters below it
        (``leaf >> level``)."""
        return leaf >> level

    # ---- compilation ----------------------------------------------------
    def tables(self, p, n: int, a: int) -> TopoTables:
        """Compile the placement + level declarations into the factored
        per-level core and bank cluster ids."""
        cc = np.asarray(self.core_clusters(p, n), np.int32)
        bc = np.asarray(self.bank_clusters(p, a), np.int32)
        if cc.shape != (n,) or bc.shape != (a,):
            raise ValueError(
                f"topology {self.name!r}: placement shapes {cc.shape}/"
                f"{bc.shape} do not match (n={n}, a={a})")
        lv = range(len(self.levels))
        return TopoTables(
            core_lvl=tuple(np.asarray(self.level_cluster(cc, i, p), np.int32)
                           for i in lv),
            bank_lvl=tuple(np.asarray(self.level_cluster(bc, i, p), np.int32)
                           for i in lv),
            extra_lat=tuple(spec.extra_lat for spec in self.levels),
            core_cluster=cc, bank_cluster=bc)
