"""Vectorized cycle-level engine of an SPM manycore (MemPool-like).

This is the **faithful reproduction** layer: the paper's claims are
behavioural properties of the synchronization protocols (retries, polling
traffic, ordering, fairness), which a cycle-level protocol simulator
reproduces exactly; silicon numbers (kGE, pJ) are treated as calibration
constants in ``core.costmodel``.

Machine model
-------------
* N cores, A addresses (≤ #banks; each contended address lives in its own
  single-ported bank — one request served per bank per cycle).
* A shared request/response network with ``lat``-cycle one-way latency and a
  global bandwidth cap of ``net_bw`` accepted requests per cycle
  (models MemPool's group-level interconnect; responsible for the Fig. 5
  interference effect).
* Every core runs a per-core **program** owned by a workload plugin
  (``core.workloads``): a micro-op table of local-work / atomic / barrier
  steps interpreted with a per-core program counter.  The default
  ``rmw_loop`` workload compiles to the seed behaviour — local work
  (``work`` cycles) → atomic RMW on a pseudo-random address (``modify``
  cycles between load and store) → repeat — and is bit-identical to the
  pre-workload engine.

Protocols
---------
What happens when an arbitrated request reaches its bank is owned by a
protocol *plugin* (``core.protocols``): the engine here keeps only the
protocol-agnostic machinery — per-core timers and state transitions, the
backoff policy, worker traffic, network acceptance with head-of-line
blocking, and per-bank FIFO arbitration.  ``PROTOCOLS`` lists the paper's
seven; ``repro.core.protocols.names()`` lists everything registered
(including ``colibri_hier`` and ``ticket_lock``).

All state lives in int32/bool arrays; one `lax.scan` step per cycle.
Scalar parameters (seed, n_addrs, lat, work, ...) may be **traced** — the
vmapped sweep runner (``core.sweep``) batches whole parameter grids
through one compilation of this engine.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from types import SimpleNamespace
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import metrics as metrics_mod
from repro.core import protocols as proto_registry
from repro.core import topologies as topo_registry
from repro.core import workloads as wl_registry
from repro.core.metrics import LAT_BINS, LAT_SUB
from repro.core.protocols.base import (BACKOFF, BARWAIT, MOD, NXT_BACKOFF,
                                       NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_EVICT, OUT_FAIL, OUT_GRANT,
                                       OUT_NONE, OUT_SLEEP, P_ACQ, P_REL,
                                       REQ, RESP, SLEEP, WORK, at_cores,
                                       fired)
from repro.core.workloads.base import (ADDR_FIXED, ADDR_ZIPF, K_BARRIER,
                                       zipf_index)
from repro.faults import DROP_DENOM, FaultPlan
from repro.kernels import engine_step
from repro.kernels.engine_step.ops import PREF_BLOCK_A, pick_block
from repro.obs.runreport import span
from repro.obs.schema import TELE_K, TELE_NSUM, window_len

#: the paper's seven protocols (Figs. 3–6); the registry may hold more.
PROTOCOLS = ("amo", "lrsc", "lrscwait", "colibri",
             "amo_lock", "lrsc_lock", "mwait_lock")

#: execution backends for the engine hot loop.  ``auto`` resolves to the
#: best backend for the visible devices (accelerator if present, else the
#: XLA scan path); ``pallas_interpret`` runs the fused Pallas kernel in
#: interpret mode on CPU — slow, but it exercises the exact kernel
#: dataflow, which is how the backend-equivalence suite pins the kernel
#: bit-identical to the scan oracle on CPU-only hosts.
BACKENDS = ("auto", "xla_cpu", "pallas_gpu", "pallas_tpu",
            "pallas_interpret")


def _has_platform(platform: str) -> bool:
    try:
        return len(jax.devices(platform)) > 0
    except RuntimeError:
        return False


def available_backends() -> tuple:
    """The subset of :data:`BACKENDS` constructible on this host (the
    pallas device backends require a matching accelerator)."""
    avail = {"auto", "xla_cpu", "pallas_interpret"}
    if _has_platform("gpu"):
        avail.add("pallas_gpu")
    if _has_platform("tpu"):
        avail.add("pallas_tpu")
    return tuple(b for b in BACKENDS if b in avail)


def resolve_backend(backend: str) -> str:
    """Map ``auto`` onto the concrete backend for the visible devices."""
    if backend == "auto":
        if _has_platform("tpu"):
            return "pallas_tpu"
        if _has_platform("gpu"):
            return "pallas_gpu"
        return "xla_cpu"
    return backend

#: SimParams fields the engine accepts as traced scalars (sweep axes).
DYN_FIELDS = ("seed", "n_addrs", "lat", "work", "modify", "backoff",
              "backoff_exp", "net_bw", "hol_block", "n_workers",
              "zipf_skew")

#: int32 sentinel for "no request" in the arbitration primitives
_BIG = jnp.iinfo(jnp.int32).max


def fused_key_fits_int32(cycles: int, n: int) -> bool:
    """Static predicate behind the arbitration-path choice: may the
    engine use the one-segment-min fused FIFO key
    ``arr_cyc * (n + 1) + rot`` for this (horizon, core count)?

    True iff the largest possible key provably stays below the int32
    ``_BIG`` sentinel (``arr_cyc < cycles``, ``rot <= n``).  The seed
    engine assumed this always held — false at ``n_cores=1024`` past
    ~2M cycles, where the product wrapped int32 and inverted the FIFO
    order (the PR 3 bug).  ``repro.analysis.int_range`` independently
    re-derives the wrap threshold by interval arithmetic and certifies
    this predicate sound and tight, so the two must never drift: the
    engine imports THIS function, the analyzer checks it.
    """
    return cycles * (n + 1) + n <= int(_BIG)

#: element ceiling for the dense (a, n) arbitration/histogram path: with
#: a small bank×core product a masked 2-D min/sum vectorizes, while an
#: n-lane scatter serializes lane by lane on CPU (~10× the cost of a
#: dense element); past this the scatter's O(n) beats the dense O(a*n).
#: Measured crossover on the 2-vCPU reference box: a=64 @ n=256 still
#: wins dense (1.26×), a=256 @ n=256 loses (0.7×).
_DENSE_BANK_ELTS = 32768

#: under the vmapped sweep the dense intermediate is (batch, a, n) —
#: once that working set spills L2 the dense path collapses (measured
#: 0.15× at 393k elements), so ``simulate`` takes the batch size as a
#: static hint and also bounds the batched element count.
_DENSE_BATCH_ELTS = 131072

#: bank count up to which a hierarchical topology looks up each lane's
#: bank-side cluster ids (``bank_lvl[ℓ][addr]``, once per cycle) by an
#: unrolled select over the banks against constants; above it, by one
#: gather per level from the (a,) table.  Measured on a TPU v5e at 1024
#: cores (cluster2): select ties the gather at a = 16..64 and wins by
#: 16% of the whole cycle at a = 128; at a = 256 it still won 14% per
#: cycle but compiled 9 s slower (18 s against 9), at 1024 it won 3%.
_TOPO_SELECT_BANKS = 128

#: cores per bank down to which per-bank results (arbitration outcomes,
#: queue wake-ups) reach the core lanes by a scatter from the banks; with
#: fewer cores per bank each core gathers from its own bank instead.  The
#: scatter form pays one index per bank in each of several ops
#: (colibri_hier: six in sim.wake, five in sim.arbitrate), the gather
#: form one index per core in three.  Measured on a TPU v5e at 1024 cores
#: (zipf_hist's machine), µs of wall per cycle, scatter / gather: a = 256
#: 68.0 / 71.2, a = 1024 160.9 / 113.6, a = 2048 291.2 / 179.5, a = 4096
#: 545.8 / 308.1; the two meet near 300 banks.
_SCATTER_CORES_PER_BANK = 4


@dataclasses.dataclass(frozen=True)
class SimParams:
    protocol: str = "colibri"
    workload: str = "rmw_loop"       # per-core program (core.workloads)
    n_cores: int = 256
    # lax.scan unroll factor: XLA fuses this many simulated cycles per
    # loop iteration.  Pure compilation knob — results are bit-identical
    # at every setting (tests/test_protocols.py re-runs the goldens at
    # unroll 2 and 8 on top of the default).  With the scatter-free hot
    # path, 1 measures fastest up to 256 cores and ~2 at 1024
    # (EXPERIMENTS.md §Engine-throughput has the ablation).
    unroll: int = 1
    # Execution backend for the hot loop (see BACKENDS): "auto" picks the
    # accelerator's fused Pallas engine-step kernel when one is visible
    # and the XLA scan path otherwise.  Pure execution knob — results are
    # bit-identical across backends (tests/test_engine_backend.py pins
    # the full protocol × workload grid).
    backend: str = "auto"
    n_addrs: int = 1                 # contention: fewer addresses = hotter
    cycles: int = 20_000
    lat: int = 5                     # one-way network latency (cycles)
    work: int = 10                   # local work between atomics
    modify: int = 4                  # cycles between load and store
    # Calibrated backoff policy: base 160 with one exponential doubling
    # reproduces the paper's headline ratios (6.5x high contention, ~13% low)
    # against its nominal "128-cycle backoff" (which sits on a very steep
    # sensitivity cliff -- see EXPERIMENTS.md §Calibration).
    backoff: int = 160               # base retry backoff
    backoff_exp: int = 2             # exponential backoff: cap base<<(exp-1)
    q_slots: int = 256               # lrscwait queue capacity (≥N ⇒ ideal)
    net_bw: int = 64                 # network acceptances per cycle
    # Head-of-line blocking: requests parked at a saturated bank back up
    # through switch buffers, each `hol_block` parked requests occupy one
    # network slot (0 disables). This is the Fig.5 interference mechanism.
    hol_block: int = 16
    n_workers: int = 0               # Fig.5: cores streaming a matmul
    seed: int = 0
    n_groups: int = 4                # colibri_hier: clusters of cores
    zipf_skew: int = 100             # 100*s for ADDR_ZIPF streams (s=1.0)
    # NoC topology (core.topologies): "flat" is the historical single
    # crossbar and compiles to NO topology tables at all — the trace is
    # bit-identical to the pre-topology engine (tests/test_topology.py
    # pins the full protocol × workload grid).  Hierarchical entries
    # ("cluster2", "cluster3") close per-level core and bank cluster ids
    # and per-level link budgets over the scan as constants: the carry
    # contract gains only the single ``hops`` counter.
    topology: str = "flat"
    clusters: int = 4                # leaf clusters (hierarchical topologies)
    record_trace: bool = False       # emit (cycles, n) completed-step trace
    # Windowed in-scan telemetry (repro.obs): > 0 carries a
    # (telemetry_windows, TELE_K) accumulator through the scan — a
    # per-window timeseries of core states, bank-access outcomes, queue
    # depths and NoC traffic, identical across backends and read back by
    # Result.timeseries().  0 (the default) statically elides the carry:
    # the trace is bit-identical to the pre-telemetry engine (an extra
    # written carry is a measured compile cliff — EXPERIMENTS.md
    # §Metric-cost / §Telemetry-cost).
    telemetry_windows: int = 0
    # Fault injection & recovery (repro.faults): a FaultPlan describing
    # deterministic seed-derived core kills/stalls, NoC message drops
    # (incl. lost wakeups) and bank stalls, plus the recovery knobs
    # (reservation watchdog_cyc -> protocol on_timeout eviction, and the
    # progress_cyc livelock/deadlock flag).  The default no-fault plan
    # statically elides every fault branch AND every extra scan carry —
    # the off path is bit-identical to the pre-fault engine
    # (tests/test_faults.py pins both, jaxpr carry count included).
    faults: FaultPlan = FaultPlan()

    # Early validation: bad names and impossible sizes fail HERE, with
    # the registry's available names in the message, instead of deep
    # inside a jit trace (or as a registry KeyError mid-``simulate``).
    # ``repro.sync.Spec`` lowers onto this, so both API layers share one
    # set of constraints and error texts.
    _BOUNDS = (("n_cores", 1), ("cycles", 1), ("n_addrs", 1),
               ("q_slots", 1), ("n_groups", 1), ("unroll", 1),
               ("backoff_exp", 1), ("net_bw", 1), ("lat", 0),
               ("work", 0), ("modify", 0), ("backoff", 0),
               ("hol_block", 0), ("n_workers", 0), ("zipf_skew", 0),
               ("telemetry_windows", 0), ("clusters", 1))

    def __post_init__(self):
        if self.protocol not in proto_registry.names():
            raise ValueError(
                f"unknown protocol {self.protocol!r}; registered protocols: "
                f"{', '.join(proto_registry.names())}")
        if self.workload not in wl_registry.names():
            raise ValueError(
                f"unknown workload {self.workload!r}; registered workloads: "
                f"{', '.join(wl_registry.names())}")
        if self.topology not in topo_registry.names():
            raise ValueError(
                f"unknown topology {self.topology!r}; registered topologies: "
                f"{', '.join(topo_registry.names())}")
        for fname, lo in self._BOUNDS:
            v = getattr(self, fname)
            if (not isinstance(v, (int, np.integer))
                    or isinstance(v, bool) or v < lo):
                raise ValueError(
                    f"{fname} must be an int >= {lo} (got {v!r})")
        if not isinstance(self.seed, (int, np.integer)) \
                or isinstance(self.seed, bool):
            raise ValueError(f"seed must be an int (got {self.seed!r})")
        if not isinstance(self.record_trace, (bool, np.bool_)):
            raise ValueError(
                f"record_trace must be a bool (got {self.record_trace!r})")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; available backends: "
                f"{', '.join(available_backends())}")
        if self.backend not in available_backends():
            dev = "TPU" if self.backend == "pallas_tpu" else "GPU"
            raise ValueError(
                f"backend {self.backend!r} requires a {dev} device and "
                f"none is visible to jax; available backends: "
                f"{', '.join(available_backends())}")
        # forgiving about shape, strict about content: None and plain
        # dicts (the JSON round-trip shape) normalize to a FaultPlan,
        # whose own __post_init__ owns the field validation
        if self.faults is None:
            object.__setattr__(self, "faults", FaultPlan())
        elif isinstance(self.faults, dict):
            object.__setattr__(self, "faults", FaultPlan(**self.faults))
        elif not isinstance(self.faults, FaultPlan):
            raise ValueError(
                f"faults must be a FaultPlan, a dict or None "
                f"(got {self.faults!r})")
        wl = wl_registry.get(self.workload)
        if self.n_addrs < wl.min_addrs:
            raise ValueError(
                f"workload {self.workload!r} needs n_addrs >= "
                f"{wl.min_addrs} (got {self.n_addrs})")


def _hash(x):
    """Cheap counter-based pseudo-random (Knuth multiplicative)."""
    return (x.astype(jnp.uint32) * jnp.uint32(2654435761)) >> 8


def accept_rotating_fair(all_req: jnp.ndarray, rot: jnp.ndarray,
                         budget, shift=None) -> jnp.ndarray:
    """Accept the ``budget`` requesters with the lowest rotated priority.

    O(n) replacement for the former per-cycle ``jnp.argsort`` ranking:
    ``rot`` is a permutation of ``[0, n)``, so laying the request mask
    out in rot-space and taking a cumulative sum yields each requester's
    exact rank among requesters (the stable argsort put all requesters
    first, ordered by ``rot``, which is the same ordering).

    For an arbitrary permutation the transpose into rot-space is a
    scatter and the rank read-back a gather.  The engine's rotation is
    *affine* — ``rot = (iota + shift) % n`` — so when ``shift`` is
    passed both turn into plain array rotations (``jnp.roll``), leaving
    the hot path scatter- and gather-free: roll, cumsum, roll back.
    The winner set is bit-identical either way —
    ``tests/test_arbitration.py`` proves both against the argsort path.
    """
    if shift is None:
        n = all_req.shape[0]
        req_by_rot = jnp.zeros((n,), jnp.int32).at[rot].set(
            all_req.astype(jnp.int32))
        rank = jnp.cumsum(req_by_rot)[rot] - 1   # rank among requesters
    else:
        req_by_rot = jnp.roll(all_req.astype(jnp.int32), shift)
        rank = jnp.roll(jnp.cumsum(req_by_rot), -shift) - 1
    return all_req & (rank < budget)


def _fifo_lex_best(arrived, arr_cyc, rot, addr, a: int):
    """Lexicographic (arrival stamp, rotated priority) segment-min.
    Returns ``(winner_mask (n,), best_rot (a,), valid (a,))`` — overflow
    -safe at any stamp magnitude (two chained int32 mins, no product)."""
    best_cyc = jnp.full((a,), _BIG, jnp.int32).at[addr].min(
        jnp.where(arrived, arr_cyc, _BIG))
    tie = arrived & (arr_cyc == best_cyc[addr])
    best_rot = jnp.full((a,), _BIG, jnp.int32).at[addr].min(
        jnp.where(tie, rot, _BIG))
    return tie & (rot == best_rot[addr]), best_rot, best_cyc != _BIG


def fifo_bank_winners(arrived: jnp.ndarray, arr_cyc: jnp.ndarray,
                      rot: jnp.ndarray, addr: jnp.ndarray,
                      a: int) -> jnp.ndarray:
    """Per-bank FIFO arbitration: the oldest arrival stamp wins its bank;
    rotating priority breaks same-cycle ties.

    Two chained segment-mins replace the former fused
    ``arr_cyc * (n + 1) + rot`` key, which silently overflowed int32 at
    ``n_cores=1024`` once ``arr_cyc`` passed ~2M cycles (the product
    exceeds 2^31), inverting the FIFO order.  Comparing stamps directly
    keeps the full int32 cycle horizon at any core count and is
    bit-identical to the key on every non-overflowing input.  (The
    engine statically picks the one-min fused key whenever
    ``cycles * (n + 1)`` provably fits int32, and this path otherwise.)
    """
    return _fifo_lex_best(arrived, arr_cyc, rot, addr, a)[0]


def topo_lookup(p: SimParams) -> str:
    """How ``simulate`` looks up the bank side of ``p``'s topology:
    ``"none"`` (flat: no lookup traced), ``"select"`` or ``"gather"``
    (by the static bank count, see ``_TOPO_SELECT_BANKS``).  The
    dispatch spans and ``ChunkRecord.topo`` record it."""
    if not topo_registry.get(p.topology).levels:
        return "none"
    return "select" if p.n_addrs <= _TOPO_SELECT_BANKS else "gather"


def bank_to_core(p: SimParams) -> str:
    """How per-bank results (arbitration outcomes, queue wake-ups) reach
    the core lanes: ``"scatter"``, each bank writing its core (one index
    per bank), while there are at least ``_SCATTER_CORES_PER_BANK`` cores
    per bank, else ``"gather"``, each core reading its own bank (one
    index per core).  Results are bit-identical.  Handed to the protocols
    as ``Ctx.to_cores``; the dispatch spans and ``ChunkRecord.to_cores``
    record it."""
    if p.n_cores >= _SCATTER_CORES_PER_BANK * p.n_addrs:
        return "scatter"
    return "gather"


def bank_tiles(p: SimParams) -> int:
    """Bank tiles of the engine-step kernel's grid for ``p`` (``a //
    block_a``), 0 on the XLA scan path, which runs no kernel."""
    if resolve_backend(p.backend) == "xla_cpu":
        return 0
    return p.n_addrs // pick_block(p.n_addrs, PREF_BLOCK_A)


def dispatch_args(p: SimParams) -> Dict[str, object]:
    """The regime a run of ``p`` traces, as the dispatch spans and
    ``ChunkRecord`` record it: the topology lookup (``topo``), the bank
    count (``banks``), the kernel grid (``bank_tiles``) and the direction
    of the bank-to-core delivery (``to_cores``)."""
    return dict(topo=topo_lookup(p), banks=p.n_addrs,
                bank_tiles=bank_tiles(p), to_cores=bank_to_core(p))


def _resolve(p: SimParams, dyn: Optional[Dict] = None) -> SimpleNamespace:
    """Parameter namespace handed to the engine and plugins.  Fields named
    in ``dyn`` become traced scalars; everything else stays a Python int
    (so the plain ``run`` path traces to exactly the constants it always
    did)."""
    vals = {f.name: getattr(p, f.name) for f in dataclasses.fields(p)}
    if dyn:
        for k, v in dyn.items():
            if k not in DYN_FIELDS:
                raise ValueError(f"{k!r} is not a sweepable field; "
                                 f"sweep axes: {DYN_FIELDS}")
            vals[k] = v
    return SimpleNamespace(**vals)


def simulate(p: SimParams, dyn: Optional[Dict] = None, batch: int = 1
             ) -> Dict[str, jnp.ndarray]:
    """One engine run.  ``p`` is static (shapes, protocol, cycle count);
    ``dyn`` optionally overrides ``DYN_FIELDS`` entries with traced
    scalars — ``p.n_addrs`` then acts as the static bank allocation upper
    bound while ``dyn["n_addrs"]`` is the live address count.  ``batch``
    is a static hint from the vmapped sweep runner: how many engine
    instances share this trace (sizes the dense-vs-scatter arbitration
    choice; never changes results)."""
    proto = proto_registry.get(p.protocol)
    wl = wl_registry.get(p.workload)
    prog = wl.program(p)
    pt = prog.tables()                   # static micro-op table (int32)
    L = prog.length
    n, a = p.n_cores, p.n_addrs
    rp = _resolve(p, dyn)
    q_cap = proto.q_cap(p, n)
    exp_cap = 1 if proto.fixed_backoff else rp.backoff_exp
    # ---- NoC topology (core.topologies) --------------------------------
    # The per-level core and bank cluster ids are compiled host-side ONCE
    # per trace and closed over as constants — same carry-cliff
    # discipline as telemetry/faults: ``flat`` compiles to is_flat and
    # every topology branch below is Python-gated off, tracing to exactly
    # the pre-topology jaxpr (tests/test_topology.py pins bit-identity).
    topo = topo_registry.get(p.topology)
    tt = topo.tables(p, n, a)
    use_topo = not tt.is_flat
    lookup = topo_lookup(p)
    to_cores = bank_to_core(p)

    state = dict(
        st=jnp.full((n,), WORK, jnp.int32),
        tmr=(jnp.arange(n, dtype=jnp.int32) * 3) % (rp.work + 1),  # stagger
        addr=jnp.zeros((n,), jnp.int32),
        phase=jnp.zeros((n,), jnp.int32),
        pc=jnp.zeros((n,), jnp.int32),           # program counter
        bar_cnt=jnp.zeros((n,), jnp.int32),      # barrier arrivals
        nxt=jnp.zeros((n,), jnp.int32),
        arr_cyc=jnp.full((n,), -1, jnp.int32),   # FIFO arrival stamp
        parked=jnp.zeros((n,), bool),            # accepted, waiting at bank
        resp_prev=jnp.zeros((), jnp.int32),      # last cycle's response load
        opc=jnp.zeros((n,), jnp.int32),          # per-core op counter
        streak=jnp.zeros((n,), jnp.int32),       # consecutive failures
        ops=jnp.zeros((n,), jnp.int32),          # completed ops
        acq_start=jnp.zeros((n,), jnp.int32),    # first-issue cycle stamp
        bank=proto.init_bank_state(p, a, n, q_cap),
        xc=proto.init_core_state(p, n),
        # stats
        msgs=jnp.zeros((), jnp.int32),
        polls=jnp.zeros((), jnp.int32),          # failed attempts (retries)
        addr_ops=jnp.zeros((a,), jnp.int32),     # completed atomics per bank
        sleep_cyc=jnp.zeros((), jnp.int32),
        bar_cyc=jnp.zeros((), jnp.int32),        # cycles parked at barriers
        lat_hist=jnp.zeros((LAT_BINS,), jnp.int32),  # completion latencies
        lat_max=jnp.zeros((), jnp.int32),        # exact worst completion
        backoff_cyc=jnp.zeros((), jnp.int32),
        active_cyc=jnp.zeros((), jnp.int32),
        bank_ops=jnp.zeros((), jnp.int32),
        net_stall=jnp.zeros((), jnp.int32),
        # Fig.5 workers: streaming loads; progress = served requests
        w_tmr=jnp.zeros((n,), jnp.int32),
        w_served=jnp.zeros((n,), jnp.int32),
    )
    # hierarchical topologies carry ONE extra scalar: total NoC hop
    # traversals (requests + responses), the quantity the per-hop energy
    # term bills.  Flat runs never carry it (and their result dicts
    # never contain "hops"), keeping the 27-key contract untouched.
    if use_topo:
        state["hops"] = jnp.zeros((), jnp.int32)
    # windowed telemetry (repro.obs): the carry exists ONLY when the
    # knob is on — a Python-level gate, so the off path traces to
    # exactly the pre-telemetry scan (the PR 4 lesson: one extra
    # written carry is a compile cliff, not a rounding error)
    use_tele = p.telemetry_windows > 0
    if use_tele:
        state["tele"] = jnp.zeros((p.telemetry_windows, TELE_K), jnp.int32)
        tele_cw = window_len(p.cycles, p.telemetry_windows)
    # ---- fault injection & recovery (repro.faults) ----------------------
    # Same carry-cliff discipline as telemetry: EVERY fault carry and
    # branch below is Python-gated on the plan, so the default no-fault
    # plan traces to exactly the pre-fault scan (bit-identical, zero
    # extra carries — tests/test_faults.py asserts the jaxpr).  Victim
    # sets are drawn host-side from the plan's seed (trace constants,
    # never carried); only the holder-kill mode needs in-scan state
    # because its victims are data-dependent (the first n_kill grantees).
    fp = p.faults
    use_faults = fp.enabled
    holder_mode = use_faults and fp.n_kill > 0 and fp.kill_holder == 1
    uni_kill = use_faults and fp.n_kill > 0 and fp.kill_holder == 0
    has_stall = use_faults and fp.n_stall > 0
    has_bstall = use_faults and fp.n_bank_stall > 0
    has_drop = use_faults and fp.msg_drop_bp > 0
    any_core_fault = holder_mode or uni_kill or has_stall
    use_wd = (use_faults and fp.watchdog_cyc > 0
              and proto.held(state["bank"]) is not None)
    if use_faults:
        kill_m = jnp.asarray(fp.kill_mask(n)) if uni_kill else None
        stall_m = jnp.asarray(fp.stall_mask(n)) if has_stall else None
        bstall_m = jnp.asarray(fp.bank_stall_mask(a)) if has_bstall else None
        n_kill_eff = min(fp.n_kill, n)
        n_stall_eff = min(fp.n_stall, n)
        n_bstall_eff = min(fp.n_bank_stall, a)
        prog_thr = fp.progress_threshold()
        state["faults_injected"] = jnp.zeros((), jnp.int32)
        state["halt_cyc"] = jnp.full((), -1, jnp.int32)   # -1: never halted
        state["last_ret"] = jnp.zeros((), jnp.int32)
        if holder_mode:
            state["kmask"] = jnp.zeros((n,), bool)        # killed holders
            state["kleft"] = jnp.full((), fp.n_kill, jnp.int32)
        if use_wd:
            state["wd_srv"] = jnp.zeros((a,), jnp.int32)  # last service cyc
            state["wd_own"] = jnp.full((a,), n, jnp.int32)  # last grantee
            state["recoveries"] = jnp.zeros((), jnp.int32)
    xc_keys = tuple(state["xc"])

    # ---- closure constants hoisted out of the scan body ----------------
    # Everything here is computed ONCE per trace instead of once per
    # simulated cycle: the core-id iota, the worker mask, the per-step
    # duration/kind tables (micro-op table entries combined with the
    # possibly-traced ``work``/``modify`` scalars), and the fixed-address
    # table.  The scan body only gathers from them at ``pc``.
    iota = jnp.arange(n, dtype=jnp.int32)
    ba = jnp.arange(a, dtype=jnp.int32)
    if use_topo:
        # a core's cluster id per level is a per-lane constant; only the
        # bank side depends on ``addr``
        core_lvl = tuple(jnp.asarray(c) for c in tt.core_lvl)
        lvl_div = tuple(lv.bw_div for lv in topo.levels)
    is_worker = iota < rp.n_workers              # first W cores are workers
    # static: worker machinery folds away when no config has workers
    # (run() always sees a Python int; sweep drops the axis when the
    # whole chunk is worker-free)
    has_workers = not (isinstance(rp.n_workers, int) and rp.n_workers == 0)
    na = rp.n_addrs
    if not isinstance(na, int):
        na = na.astype(jnp.uint32)
    pre_dur_tab = pt["pre_mult"] * rp.work + pt["pre_add"]      # (L,)
    mod_dur_tab = pt["mod_mult"] * rp.modify + pt["mod_add"]    # (L,)
    kind_is_bar = pt["kind"] == K_BARRIER                       # (L,)
    mode_is_fix = pt["addr_mode"] == ADDR_FIXED                 # (L,)
    mode_is_zipf = pt["addr_mode"] == ADDR_ZIPF                 # (L,)
    fix_tab = (pt["addr_arg"].astype(jnp.uint32) % na).astype(jnp.int32)
    has_zipf = bool(np.any(np.asarray(prog.addr_mode) == ADDR_ZIPF))
    has_bar = bool(np.any(np.asarray(prog.kind) == K_BARRIER))
    # static: can the fused FIFO key arr_cyc*(n+1)+rot ever leave int32?
    # (arr_cyc < cycles, rot <= n).  The seed engine assumed it never
    # did — false at n=1024 past ~2M cycles — so the safe two-stage
    # arbiter kicks in exactly where the old key wrapped.
    key_fits_int32 = fused_key_fits_int32(p.cycles, n)
    # execution backend: the fused Pallas engine-step kernel replaces
    # the arbitration + protocol + histogram stages of the scan body;
    # everything around it (issue, retire, network, wakeups) is shared
    bk = resolve_backend(p.backend)
    use_pallas = bk != "xla_cpu"
    pl_interpret = bk == "pallas_interpret"
    dense_banks = (a * n <= _DENSE_BANK_ELTS
                   and a * n * max(batch, 1) <= _DENSE_BATCH_ELTS)
    # same dense-vs-scatter choice for the latency histogram accumulator
    # (it runs over the a bank lanes; LAT_BINS plays the bank-count role)
    lbins = jnp.arange(LAT_BINS, dtype=jnp.int32)
    dense_lat = (LAT_BINS * a <= _DENSE_BANK_ELTS
                 and LAT_BINS * a * max(batch, 1) <= _DENSE_BATCH_ELTS)

    def step_addr(opc, pc):
        """Current micro-op's target address.  The uniform stream is the
        seed engine's counter hash, bit-identical under ``rmw_loop``."""
        h = _hash(iota * 7919 + opc * 104729 + rp.seed)
        uni = (h % na).astype(jnp.int32)
        out = jnp.where(mode_is_fix[pc], fix_tab[pc], uni)
        if has_zipf:
            out = jnp.where(mode_is_zipf[pc],
                            zipf_index(h, rp.n_addrs, rp.zipf_skew), out)
        return out

    def crossings(addr):
        """Per level, whether each lane's (core, ``addr``) path crosses
        it: ``core_lvl[ℓ] != bank_lvl[ℓ][addr]``.  The bank-side id is a
        select chain over the banks (starting from the commonest id, so
        banks holding it cost nothing) or, past ``_TOPO_SELECT_BANKS``,
        one gather from the (a,) table."""
        out = []
        for cl, bl in zip(core_lvl, tt.bank_lvl):
            if lookup == "select":
                vals, cnt = np.unique(bl, return_counts=True)
                base = int(vals[np.argmax(cnt)])
                b_id = jnp.full(addr.shape, base, jnp.int32)
                for b in np.flatnonzero(bl != base):
                    b_id = jnp.where(addr == int(b), int(bl[b]), b_id)
            else:
                b_id = jnp.asarray(bl)[addr]
            out.append(cl != b_id)
        return out

    # Each stage of a simulated cycle runs under a ``jax.named_scope``
    # (sim.issue, sim.retire, sim.network, sim.arbitrate, sim.wake,
    # sim.account, sim.telemetry, sim.faults).  A scope is op-name
    # metadata in the compiled program, so a profiler trace attributes
    # device time to stages; it changes neither the jaxpr nor a result.
    def step(s, cyc):
        st, tmr, pc = s["st"], s["tmr"], s["pc"]
        # ---- timers ----
        with jax.named_scope("sim.issue"):
            tmr = jnp.maximum(tmr - 1, 0)
            t0 = tmr == 0

        # ---- fault injection: dead/stalled cores freeze ----
        # dead = permanently killed ∪ inside a transient stall window.
        # A dead core freezes (timers never fire, no new requests, no
        # retransmits) but requests already in flight still get served —
        # if one was granted a reservation, the bank wedges: exactly the
        # failure the reservation watchdog exists for.
        with jax.named_scope("sim.faults"):
            if any_core_fault:
                if holder_mode:
                    killed = s["kmask"]
                elif uni_kill:
                    killed = kill_m & (cyc >= fp.kill_cyc)
                else:
                    killed = jnp.zeros((n,), bool)
                dead = killed
                if has_stall:
                    dead = dead | (stall_m & (cyc >= fp.stall_cyc)
                                   & (cyc < fp.stall_cyc + fp.stall_dur))
                t0 = t0 & ~dead
            if use_faults:
                finj = s["faults_injected"]
                if uni_kill:
                    finj = finj + jnp.where(cyc == fp.kill_cyc, n_kill_eff, 0)
                if has_stall:
                    finj = finj + jnp.where(cyc == fp.stall_cyc,
                                            n_stall_eff, 0)
                if has_bstall:
                    finj = finj + jnp.where(cyc == fp.bank_stall_cyc,
                                            n_bstall_eff, 0)

        # ---- timer-expiry dispatch (one predicated block) ----
        # WORK -> issue current micro-op's acquire; BACKOFF -> reissue
        # acquire; MOD -> issue release/SC.  The three source states are
        # mutually exclusive, so a single fused REQ/latency write covers
        # what used to be three identical where-chains.
        with jax.named_scope("sim.issue"):
            start = t0 & (st == WORK) & ~is_worker
            rb = t0 & (st == BACKOFF)
            md = t0 & (st == MOD)
            issue = start | rb | md
            addr = jnp.where(start, step_addr(s["opc"], pc), s["addr"])
            phase = jnp.where(md, P_REL,
                              jnp.where(start | rb, P_ACQ, s["phase"]))
            st = jnp.where(issue, REQ, st)
            if use_topo:
                # ``addr`` is final from here on: look up its crossings once
                # for the extra latency, link budgets, hops and telemetry.
                # Cross-cluster requests pay the per-level extra latency once
                # per issue (acquire, reissue, release) — the round-trip cost
                # of the level routers on top of the flat ``lat`` baseline.
                # Billed HERE, before the request reaches the network/bank
                # stages, so protocols and the Pallas kernel never see
                # topology: backends stay bit-identical by construction.
                cm = crossings(addr)
                x_lat = sum(lat * x for lat, x in zip(tt.extra_lat, cm))
                tmr = jnp.where(issue, rp.lat + x_lat, tmr)
            else:
                tmr = jnp.where(issue, rp.lat, tmr)

        # ---- RESP arrives: the current micro-op retires ----
        with jax.named_scope("sim.retire"):
            ra = t0 & (st == RESP)
            done = ra & (s["nxt"] == NXT_WORK_DONE)
            at_bar = done & kind_is_bar[pc]
            pc_next = (pc + 1) % L
            wrap = done & (pc_next == 0)             # program completed one op
            go_work = done & ~at_bar
            st = jnp.where(go_work, WORK, st)
            st = jnp.where(at_bar, BARWAIT, st)
            pc = jnp.where(done, pc_next, pc)
            # next step's local work (current step's for non-retiring cores)
            pre_dur = pre_dur_tab[pc]
            tmr = jnp.where(go_work, pre_dur, tmr)
            ops = s["ops"] + wrap
            opc = s["opc"] + done
            bar_cnt = s["bar_cnt"] + at_bar
            # completion-latency stamp: ``start`` (st==WORK, fresh micro-op)
            # and ``done`` (st==RESP) are mutually exclusive within a cycle,
            # so the stamp always predates the retirement that reads it;
            # retries (BACKOFF reissues) and queue waits keep the original
            # stamp and therefore count toward the op's latency.
            acq_start = jnp.where(start, cyc, s["acq_start"])
            if dense_banks:
                addr_ops = s["addr_ops"] + jnp.sum(
                    (addr[None, :] == ba[:, None]) & done[None, :], axis=1)
            else:
                addr_ops = s["addr_ops"].at[jnp.where(done, addr, a)].add(
                    1, mode="drop")
            to_mod = ra & (s["nxt"] == NXT_MOD)
            mod_dur = mod_dur_tab[pc]
            st = jnp.where(to_mod, MOD, st)
            tmr = jnp.where(to_mod, mod_dur, tmr)
            to_bo = ra & (s["nxt"] == NXT_BACKOFF)
            st = jnp.where(to_bo, BACKOFF, st)
            # lock protocols use the paper's stated FIXED backoff (Fig. 4 /
            # Table II: "spin locks with a backoff of 128 cycles"); bare LRSC
            # uses the calibrated exponential policy.
            streak = jnp.where(to_bo, jnp.minimum(s["streak"] + 1, exp_cap),
                               jnp.where(done, 0, s["streak"]))
            bo_len = (rp.backoff << jnp.maximum(streak - 1, 0)) + (_hash(
                iota + cyc) % 32).astype(jnp.int32)
            tmr = jnp.where(to_bo, bo_len, tmr)

            # ---- barrier: last arrival releases every waiter (broadcast) ----
            bar_msgs = jnp.zeros((), jnp.int32)
            if has_bar:
                min_bar = jnp.min(jnp.where(is_worker, _BIG, bar_cnt))
                rel_bar = (st == BARWAIT) & (bar_cnt <= min_bar)
                st = jnp.where(rel_bar, WORK, st)
                tmr = jnp.where(rel_bar, rp.lat + pre_dur, tmr)
                bar_msgs = rel_bar.sum().astype(jnp.int32)  # one wake msg each

            # ---- workers stream loads (Fig. 5) ----
            # the w_tmr/w_served updates are statically elided when the
            # trace has no workers: the writes are semantically dead at
            # n_workers == 0 but XLA cannot prove it, and two extra written
            # (n,) carries push the scan body over a compile cliff (~3×
            # wall time at 256 cores — EXPERIMENTS.md §Metric-cost)
            if has_workers:
                w_tmr = jnp.maximum(s["w_tmr"] - 1, 0)
                w_arr = is_worker & (w_tmr == 0)     # a load arrives at a bank
                if any_core_fault:
                    w_arr = w_arr & ~dead            # dead workers go silent
            else:
                w_tmr = s["w_tmr"]
                w_arr = jnp.zeros((n,), bool)

        # ---- network acceptance (rotating-fair, bounded bandwidth) ----
        with jax.named_scope("sim.network"):
            # A new request consumes one network slot ONCE; accepted
            # requests are "parked" in the bank input queue and no longer
            # use the network.
            fresh = (st == REQ) & (tmr == 0) & ~is_worker & ~s["parked"]
            if any_core_fault:
                fresh = fresh & ~dead                # dead cores stop sending
            shift = (cyc * 97) % n
            rot = (iota + shift) % n
            all_req = fresh | w_arr
            # responses issued last cycle share the same links, and parked
            # requests at saturated banks back up through switch buffers
            # (head-of-line blocking): both shrink the request budget.
            if isinstance(rp.hol_block, int):
                hol = ((s["parked"].sum() // rp.hol_block)
                       if rp.hol_block else 0)
            else:
                hol = jnp.where(
                    rp.hol_block > 0,
                    s["parked"].sum() // jnp.maximum(rp.hol_block, 1), 0)
            budget = jnp.maximum(rp.net_bw - s["resp_prev"] - hol, 1)
            accepted = accept_rotating_fair(all_req, rot, budget, shift=shift)
            if use_topo:
                # per-level link capacity: a request whose (core, bank) path
                # crosses level ℓ must ALSO win one of that level's
                # ``net_bw // bw_div`` link slots this cycle (same rotating-
                # fair arbiter, same rotation — fairness is preserved level
                # by level).  Rejected requesters stay fresh and retry next
                # cycle; they count into net_stall like any denied request.
                # Worker streams stay cluster-local (their banks are the
                # local SPM ports), so only atomic requests contend here.
                xmask = [x & ~is_worker for x in cm]
                for xm, div in zip(xmask, lvl_div):
                    acc_x = accept_rotating_fair(
                        all_req & xm, rot, jnp.maximum(rp.net_bw // div, 1),
                        shift=shift)
                    accepted = accepted & (~xm | acc_x)
            # Bernoulli NoC drop on newly-accepted requests: the message
            # dies in flight, the core stays in REQ and retransmits next
            # cycle; the wasted link hop is billed into msgs below
            with jax.named_scope("sim.faults"):
                if has_drop:
                    u = _hash(iota * 9781 + cyc * 6271
                              + fp.fault_seed * 977 + 13)
                    req_drop = (fresh & accepted
                                & ((u % DROP_DENOM) < fp.msg_drop_bp))
                    accepted = accepted & ~req_drop
                    n_req_drop = req_drop.sum()
                    finj = finj + n_req_drop
            w_acc = w_arr & accepted
            if has_workers:
                w_served = s["w_served"] + w_acc
                w_tmr = jnp.where(w_acc, 2, w_tmr)   # pipelined loads
                w_tmr = jnp.where(is_worker & (w_tmr == 0), 1, w_tmr)
            else:
                w_served = s["w_served"]
            stall_now = (all_req & ~accepted).sum()
            net_stall = s["net_stall"] + stall_now
            parked = s["parked"] | (fresh & accepted)
            arr_cyc = jnp.where(fresh & accepted, cyc, s["arr_cyc"])
            if use_topo:
                # hop accounting for the energy model: every accepted
                # request traverses its (core, bank) hop path twice (request
                # + response); accepted worker loads are cluster-local
                # single-hop round trips.
                hops_lane = 1 + 2 * sum(x.astype(jnp.int32) for x in cm)
                hops_cnt = (s["hops"]
                            + 2 * jnp.where(fresh & accepted,
                                            hops_lane, 0).sum()
                            + 2 * w_acc.sum())

        # ---- bank arbitration: FIFO by arrival stamp among parked ----
        with jax.named_scope("sim.arbitrate"):
            arrived = parked & (st == REQ)
            # bank-stall window: stalled banks accept no requests (parked
            # requesters keep waiting); masking the arbitration INPUT makes
            # the scan and pallas paths identical by construction (the
            # kernel sees the masked cand_cyc)
            with jax.named_scope("sim.faults"):
                if has_bstall:
                    bs_now = ((cyc >= fp.bank_stall_cyc)
                              & (cyc < fp.bank_stall_cyc + fp.bank_stall_dur))
                    arrived = arrived & ~(bstall_m[addr] & bs_now)
            if use_pallas:
                # fused engine-step kernel (repro.kernels.engine_step):
                # arbitration + protocol bank update + latency histogram in
                # one tiled pass over (a, n); the engine hands the per-bank
                # outcome codes back to the winning cores below (scattered
                # from the banks, or gathered by each core at its own bank:
                # ``bank_to_core``) — exactly the (st, tmr, nxt) writes
                # on_access performs via masked wheres, so the two paths
                # stay bit-identical (tests/test_engine_backend.py).
                fs = engine_step.fused_step(
                    proto, p, dict(s["bank"]),
                    cand_cyc=jnp.where(arrived, arr_cyc, _BIG),
                    rot=rot, addr=addr, phase=phase, acq_start=acq_start,
                    core={f: s["xc"][f] for f in proto.fused_core_fields},
                    cyc=cyc, shift=shift, lat=rp.lat,
                    n=n, a=a, q_cap=q_cap, cycles=p.cycles,
                    interpret=pl_interpret)
                valid_b, win_core, kind = fs["valid"], fs["win"], fs["kind"]
                xsets = [fs["xset"][f] for f in proto.fused_xset_fields]
                if to_cores == "gather":
                    # each core reads its own bank's outcome row: one
                    # gather of n indices, and the writes below are selects
                    fire_c, kind_c, tmr_c, *xs_c = at_cores(
                        addr, jnp.where(valid_b, win_core, n), kind,
                        fs["tmr"], *[r for vm in xsets for r in vm])
                    xsets = list(zip(xs_c[0::2], xs_c[1::2]))
                    winner = fired(to_cores, fire_c, iota)
                else:
                    winner = jnp.zeros((n,), bool).at[
                        jnp.where(valid_b, win_core, n)].set(True, mode="drop")
                parked = parked & ~winner                    # served
                arr_cyc = jnp.where(winner, -1, arr_cyc)
                wcs = jnp.minimum(win_core, n - 1)           # gather-safe
                acq_b = valid_b & (phase[wcs] == P_ACQ)
                rel_b = valid_b & (phase[wcs] == P_REL)
                is_acq = winner & (phase == P_ACQ)
                is_rel = winner & (phase == P_REL)
                if to_cores == "gather":
                    resp_c = winner & ((kind_c == OUT_GRANT)
                                       | (kind_c == OUT_DONE)
                                       | (kind_c == OUT_FAIL))
                    st = jnp.where(resp_c, RESP, st)
                    st = jnp.where(winner & (kind_c == OUT_SLEEP), SLEEP, st)
                    tmr = jnp.where(resp_c, tmr_c, tmr)
                    nxt_c = jnp.where(
                        kind_c == OUT_GRANT, NXT_MOD,
                        jnp.where(kind_c == OUT_DONE, NXT_WORK_DONE,
                                  NXT_BACKOFF)).astype(jnp.int32)
                    nxt = jnp.where(resp_c, nxt_c, s["nxt"])
                else:
                    resp_k = ((kind == OUT_GRANT) | (kind == OUT_DONE)
                              | (kind == OUT_FAIL))
                    rw = jnp.where(resp_k, win_core, n)
                    st = st.at[rw].set(RESP, mode="drop")
                    st = st.at[jnp.where(kind == OUT_SLEEP, win_core, n)].set(
                        SLEEP, mode="drop")
                    tmr = tmr.at[rw].set(fs["tmr"], mode="drop")
                    nxt_code = jnp.where(
                        kind == OUT_GRANT, NXT_MOD,
                        jnp.where(kind == OUT_DONE, NXT_WORK_DONE,
                                  NXT_BACKOFF)).astype(jnp.int32)
                    nxt = s["nxt"].at[rw].set(nxt_code, mode="drop")
                cs = dict(st=st, tmr=tmr, nxt=nxt,
                          polls=s["polls"] + fs["polls"],
                          msgs=(s["msgs"] + 2 * winner.sum() + bar_msgs
                                + fs["msgs"]),
                          **{k: s["xc"][k] for k in xc_keys})
                # protocol per-core writes (e.g. the ticket lock's drawn
                # ticket) come back as (values, mask) pairs
                for f, (val, msk) in zip(proto.fused_xset_fields, xsets):
                    if to_cores == "gather":
                        cs[f] = jnp.where(winner & msk, val, cs[f])
                    else:
                        cs[f] = cs[f].at[jnp.where(msk, win_core, n)].set(
                            val, mode="drop")
                bank = fs["bank"]
                ctx = proto_registry.Ctx(p=rp, n=n, a=a, q_cap=q_cap,
                                         is_acq=is_acq, is_rel=is_rel,
                                         wa=addr, wc=iota, ba=ba,
                                         win_core=win_core, acq_b=acq_b,
                                         rel_b=rel_b,
                                         mod_dur=mod_dur, to_cores=to_cores)
            else:
                if key_fits_int32:
                    # fused lexicographic key, one segment-min (the common
                    # case: the horizon is known at trace time to keep it
                    # in int32)
                    bkey = jnp.where(arrived, arr_cyc * (n + 1) + rot, _BIG)
                    if dense_banks:        # few banks: vectorized 2-D min
                        best = jnp.min(jnp.where(addr[None, :] == ba[:, None],
                                                 bkey[None, :], _BIG), axis=1)
                    else:                  # many banks: one segment-min
                        best = jnp.full((a,), _BIG, jnp.int32).at[addr].min(
                            bkey)
                    winner = arrived & (bkey == best[addr])
                    valid_b = best != _BIG
                    rot_w = best % (n + 1)   # key encodes the winner's rot
                else:
                    # long horizons: chained segment-mins, no overflow
                    winner, rot_w, valid_b = _fifo_lex_best(arrived, arr_cyc,
                                                            rot, addr, a)
                parked = parked & ~winner                    # served
                arr_cyc = jnp.where(winner, -1, arr_cyc)
                # decode each bank's winning CORE from its winning rot (the
                # rotation is affine) — protocols use it to update bank state
                # densely, O(a) instead of an n-lane scatter per array
                win_core = jnp.where(valid_b, (rot_w - shift) % n, n)
                wcs = jnp.minimum(win_core, n - 1)           # gather-safe

                # ---- protocol plugin handles the bank winners ----
                is_acq = winner & (phase == P_ACQ)
                is_rel = winner & (phase == P_REL)
                acq_b = valid_b & (phase[wcs] == P_ACQ)
                rel_b = valid_b & (phase[wcs] == P_REL)
                cs = dict(st=st, tmr=tmr, nxt=s["nxt"], polls=s["polls"],
                          msgs=s["msgs"] + 2 * winner.sum() + bar_msgs,
                          **{k: s["xc"][k] for k in xc_keys})
                ctx = proto_registry.Ctx(p=rp, n=n, a=a, q_cap=q_cap,
                                         is_acq=is_acq, is_rel=is_rel,
                                         wa=addr, wc=iota, ba=ba,
                                         win_core=win_core, acq_b=acq_b,
                                         rel_b=rel_b,
                                         mod_dur=mod_dur, to_cores=to_cores)
                cs, bank = proto.on_access(ctx, cs, dict(s["bank"]))
            bank_ops = s["bank_ops"] + winner.sum()

        # ---- telemetry: bank-access outcome tallies (pre-wake) ----
        # Derived generically instead of per-protocol: on the pallas
        # path the kernel already emits OUT_* codes per bank; on the
        # scan path the same four classes are recovered from the (st,
        # nxt) values on_access just wrote at each bank's winner — the
        # exact inverse of the engine's OUT_*->(st, nxt) apply mapping
        # (see core.protocols.base), so both backends tally identically.
        # O(a) gathers; captured BEFORE on_wake so wake-ups never
        # shadow this cycle's outcomes.
        with jax.named_scope("sim.telemetry"):
            if use_tele:
                if use_pallas:
                    oc = engine_step.outcome_counts(fs["kind"])
                else:
                    st_b, nxt_b = cs["st"][wcs], cs["nxt"][wcs]
                    resp_b = valid_b & (st_b == RESP)
                    oc = dict(
                        grants=(resp_b & (nxt_b == NXT_MOD)).sum(),
                        retires=(resp_b & (nxt_b == NXT_WORK_DONE)).sum(),
                        fails=(resp_b & (nxt_b == NXT_BACKOFF)).sum(),
                        enqueues=(valid_b & (st_b == SLEEP)).sum())
        if use_tele or use_wd or holder_mode:
            st_pre_wake = cs["st"]

        # ---- wakeups (queue-based protocols) ----
        # lost wakeup: a wake message firing this cycle drops with
        # msg_drop_bp probability — the sleeping head never hears it.
        # Without a watchdog the bank wedges forever; this is the
        # classic lost-wakeup hazard recovery must cover.
        with jax.named_scope("sim.wake"):
            wake_load = jnp.zeros((), jnp.int32)
            with jax.named_scope("sim.faults"):
                if proto.uses_queue and has_drop:
                    wt = bank["wake_tmr"]
                    uw = _hash(ba * 3643 + cyc * 9176
                               + fp.fault_seed * 389 + 7)
                    wdrop = (wt == 1) & ((uw % DROP_DENOM) < fp.msg_drop_bp)
                    bank["wake_tmr"] = jnp.where(wdrop, 0, wt)
                    finj = finj + wdrop.sum()
            if proto.uses_queue:
                cs, bank, wake_load = proto.on_wake(ctx, cs, bank)

        # ---- fault recovery: holder kills + reservation watchdog ----
        with jax.named_scope("sim.faults"):
            if holder_mode or use_wd:
                # per-bank grant/retire flags.  Pallas: straight from the
                # kernel's outcome codes; scan: recovered from the (st, nxt)
                # the protocol wrote at each winner.  Reading AFTER on_wake
                # is still exact — a winner was REQ this cycle, never
                # sleeping, so on_wake cannot have touched it.
                if use_pallas:
                    grant_bk = fs["kind"] == OUT_GRANT
                    retire_bk = fs["kind"] == OUT_DONE
                else:
                    stb, nxb = cs["st"][wcs], cs["nxt"][wcs]
                    grant_bk = valid_b & (stb == RESP) & (nxb == NXT_MOD)
                    retire_bk = valid_b & (stb == RESP) & (nxb
                                                           == NXT_WORK_DONE)
                # queue protocols hand ownership over by WAKE after warmup
                # (a bank-side OUT_GRANT needs an empty queue) — a woken
                # core is the new owner just as much as a granted one
                woken = (((st_pre_wake == SLEEP) & (cs["st"] != SLEEP))
                         if proto.uses_queue else jnp.zeros((n,), bool))
            if holder_mode:
                # targeted holder kill: the first n_kill cores handed
                # ownership (bank grant or wake) at or after kill_cyc die
                # while holding — the adversarial case (reservation/lock
                # owner vanishes mid-critical-section)
                gcore = jnp.zeros((n,), bool).at[
                    jnp.where(grant_bk, win_core, n)].set(True, mode="drop")
                cand = (gcore | woken) & (cyc >= fp.kill_cyc) & ~s["kmask"]
                rank = jnp.cumsum(cand.astype(jnp.int32)) - 1
                newk = cand & (rank < s["kleft"])
                kmask = s["kmask"] | newk
                kleft = s["kleft"] - newk.sum()
                finj = finj + newk.sum()
                killed = kmask                       # includes this cycle's
            if use_wd:
                # reservation watchdog: per-bank service timer, re-armed on
                # every sign of life (not held / a retire / a wake handoff).
                # Grants do NOT re-arm it — under lrsc a dead holder lets
                # doomed LRs keep "granting" forever, which is exactly the
                # livelock the watchdog must see through.
                held_b = proto.held(bank)
                wd_own = jnp.where(grant_bk, win_core, s["wd_own"])
                wd_own = wd_own.at[jnp.where(woken, addr, a)].set(
                    iota, mode="drop")
                wd_srv = jnp.where(~held_b | retire_bk, cyc, s["wd_srv"])
                wd_srv = wd_srv.at[jnp.where(woken, addr, a)].set(
                    cyc, mode="drop")
                stuck_b = held_b & (cyc - wd_srv >= fp.watchdog_cyc)
                killed_perm = (killed if (holder_mode or uni_kill)
                               else jnp.zeros((n,), bool))
                cs, bank, rkind = proto.on_timeout(ctx, cs, bank, stuck_b,
                                                   killed_perm, wd_own)
                recoveries = s["recoveries"] + (rkind != OUT_NONE).sum()
                wd_srv = jnp.where(stuck_b, cyc, wd_srv)     # re-arm
                # an eviction vacates the bank: forget the owner, else a
                # second timeout blames the dead core again and (e.g. for
                # ticket_lock) skips a LIVE waiter's turn — the next grant
                # or wake re-learns it
                wd_own = jnp.where(rkind == OUT_EVICT, n, wd_own)
            if use_faults:
                # forward-progress watchdog: no retirement anywhere for
                # prog_thr cycles => flag the halt cycle (detected livelock/
                # deadlock — the run completes and reports, never hangs)
                last_ret = jnp.where(done.any(), cyc, s["last_ret"])
                halt_cyc = jnp.where(
                    (s["halt_cyc"] < 0) & (cyc - last_ret >= prog_thr),
                    cyc, s["halt_cyc"])

        # network slots consumed by this cycle's responses and protocol
        # side-messages (SuccessorUpdate / WakeUpRequest / Mwait setup)
        st, tmr = cs["st"], cs["tmr"]

        # ---- completion-latency histogram (bank-side accumulation) ----
        # Every retirement is the timer expiry of a response granted at
        # a bank this cycle (protocols set st=RESP/nxt=WORK_DONE only at
        # service time and never disturb a RESP core), and arbitration
        # guarantees at most one winner per bank — so the histogram
        # update runs over the ``a`` bank lanes instead of the ``n``
        # core lanes (a is 1–16 in the hot benchmarks; the core-side
        # form measured +12 µs/cycle at 256 cores).  The grant retires
        # at ``cyc + max(tmr, 1)``; grants whose retirement falls past
        # the horizon are excluded so the histogram mass equals the
        # retired-op count exactly (the base workload invariant).  On
        # the pallas backends the kernel already accumulated this
        # cycle's rows (OUT_DONE grants are exactly the RESP/WORK_DONE
        # winners, and on_wake never touches them).
        with jax.named_scope("sim.account"):
            if use_pallas:
                lat_hist = s["lat_hist"] + fs["hist"]
                lat_max = jnp.maximum(s["lat_max"], fs["lat_max"])
            else:
                fut = valid_b & (st[wcs] == RESP) & (cs["nxt"][wcs]
                                                     == NXT_WORK_DONE)
                done_cyc = cyc + jnp.maximum(tmr[wcs], 1)
                fut = fut & (done_cyc < p.cycles)
                lat_b = done_cyc - acq_start[wcs]
                lbkt = jnp.clip((LAT_SUB * jnp.log2(
                    lat_b.astype(jnp.float32) + 1.0)).astype(jnp.int32),
                    0, LAT_BINS - 1)
                if dense_lat:
                    lat_hist = s["lat_hist"] + jnp.sum(
                        (lbkt[None, :] == lbins[:, None]) & fut[None, :],
                        axis=1)
                else:
                    lat_hist = s["lat_hist"].at[
                        jnp.where(fut, lbkt, LAT_BINS)].add(1, mode="drop")
                lat_max = jnp.maximum(s["lat_max"],
                                      jnp.max(jnp.where(fut, lat_b, 0)))
        with jax.named_scope("sim.wake"):
            extra = cs["msgs"] - s["msgs"] - 2 * winner.sum()
            resp_load = winner.sum() + w_acc.sum() + extra + wake_load
            with jax.named_scope("sim.faults"):
                if has_drop:
                    # the dropped request traversed the NoC once before dying;
                    # billed after ``extra`` so it never occupies a
                    # response slot
                    cs["msgs"] = cs["msgs"] + n_req_drop
        # per-cycle state census, shared by the cumulative stats and the
        # telemetry row (hoisted so telemetry adds no second n-lane pass)
        with jax.named_scope("sim.account"):
            sleep_now = (st == SLEEP).sum()
            bar_now = (st == BARWAIT).sum()
            backoff_now = (st == BACKOFF).sum()
            active_now = ((st != SLEEP) & (st != BARWAIT) & ~is_worker).sum()
            sleep_cyc = s["sleep_cyc"] + sleep_now
            bar_cyc = s["bar_cyc"] + bar_now
            backoff_cyc = s["backoff_cyc"] + backoff_now
            active_cyc = s["active_cyc"] + active_now

            # ---- end-of-cycle queue depths (telemetry + event trace) ----
            # per-bank reservation-queue occupancy via the protocol's
            # queue_depth view (None for queueless protocols -> zeros); read
            # AFTER on_wake so popped heads are reflected
            if use_tele or p.record_trace:
                qd = proto.queue_depth(bank)
                qd = (jnp.zeros((a,), jnp.int32) if qd is None
                      else qd.astype(jnp.int32))
        out = dict(st=st, tmr=tmr, addr=addr, phase=phase, nxt=cs["nxt"],
                   pc=pc, bar_cnt=bar_cnt,
                   opc=opc, arr_cyc=arr_cyc, streak=streak, parked=parked,
                   resp_prev=resp_load.astype(jnp.int32),
                   ops=ops, acq_start=acq_start, bank=bank,
                   xc={k: cs[k] for k in xc_keys},
                   msgs=cs["msgs"], polls=cs["polls"], addr_ops=addr_ops,
                   sleep_cyc=sleep_cyc, bar_cyc=bar_cyc,
                   lat_hist=lat_hist, lat_max=lat_max,
                   active_cyc=active_cyc,
                   backoff_cyc=backoff_cyc,
                   bank_ops=bank_ops, net_stall=net_stall,
                   w_tmr=w_tmr, w_served=w_served)
        if use_topo:
            out["hops"] = hops_cnt
        if use_faults:
            out["faults_injected"] = finj
            out["last_ret"] = last_ret
            out["halt_cyc"] = halt_cyc
            if holder_mode:
                out["kmask"], out["kleft"] = kmask, kleft
            if use_wd:
                out["wd_srv"], out["wd_own"] = wd_srv, wd_own
                out["recoveries"] = recoveries
        # ---- telemetry accumulation: one window row per cycle ----
        # cyc // tele_cw is overflow-free (tele_cw is a static ceil
        # division; no cyc * n_windows product).  Column order follows
        # obs.schema.TELE_CHANNELS; the final queue_max column is
        # max-accumulated, everything else summed.
        with jax.named_scope("sim.telemetry"):
            if use_tele:
                wakes = (((st_pre_wake == SLEEP) & (st != SLEEP)).sum()
                         if proto.uses_queue else jnp.zeros((), jnp.int32))
                # NoC link locality: accepted requests split by whether the
                # (core, bank) path crosses the leaf-cluster boundary.  On
                # the flat topology the split is the Python constant
                # "everything local" — no extra work traced.
                if use_topo:
                    xcl_now = (accepted & xmask[0]).sum().astype(jnp.int32)
                else:
                    xcl_now = jnp.zeros((), jnp.int32)
                loc_now = accepted.sum().astype(jnp.int32) - xcl_now
                row = jnp.stack([active_now, sleep_now, backoff_now, bar_now,
                                 oc["grants"], oc["retires"], oc["fails"],
                                 oc["enqueues"], wakes, cs["msgs"] - s["msgs"],
                                 stall_now, loc_now, xcl_now,
                                 qd.sum()]).astype(jnp.int32)
                w = cyc // tele_cw
                tele = s["tele"].at[w, :TELE_NSUM].add(row)
                out["tele"] = tele.at[w, TELE_NSUM].max(qd.max())
        # completion trace: which micro-op (pre-advance pc) retired where,
        # how long it took from first acquire issue to retirement, plus
        # the per-cycle state/queue-depth traces behind Result.events()
        # and the Perfetto export (repro.obs)
        with jax.named_scope("sim.account"):
            ev = (dict(step=jnp.where(done, s["pc"], -1).astype(jnp.int32),
                       wait=jnp.where(done, cyc - s["acq_start"],
                                      -1).astype(jnp.int32),
                       state=st.astype(jnp.int8), qlen=qd)
                  if p.record_trace else None)
        return out, ev

    final, trace = lax.scan(step, state,
                            jnp.arange(p.cycles, dtype=jnp.int32),
                            unroll=max(int(p.unroll), 1))
    # flatten protocol state into the result dict (names never collide
    # with engine keys)
    flat = {k: v for k, v in final.items() if k not in ("bank", "xc")}
    flat.update(final["bank"])
    flat.update(final["xc"])
    if use_faults:
        # dead-at-horizon core mask for the survivor metrics (holder
        # kills come from the carry; scheduled kills/stalls are trace
        # constants — vmap broadcasts them across the batch dim)
        dm = final["kmask"] if holder_mode else jnp.zeros((n,), bool)
        if uni_kill and fp.kill_cyc < p.cycles:
            dm = dm | kill_m
        if has_stall and (fp.stall_cyc <= p.cycles - 1
                          < fp.stall_cyc + fp.stall_dur):
            dm = dm | stall_m
        flat["dead_mask"] = dm
        if not use_wd:
            flat["recoveries"] = jnp.zeros((), jnp.int32)
    if p.record_trace:
        flat["trace_step"] = trace["step"]
        flat["trace_wait"] = trace["wait"]
        flat["trace_state"] = trace["state"]
        flat["trace_qlen"] = trace["qlen"]
    return flat


@partial(jax.jit, static_argnums=0)
def _run(p: SimParams):
    return simulate(p)


def derive_metrics(res: Dict[str, np.ndarray], n_workers: int, cycles: int,
                   energy_fit=None) -> Dict[str, np.ndarray]:
    """Attach the paper's full metric set to a raw result dict — thin
    alias for :func:`repro.core.metrics.attach`, the single derivation
    layer shared with the sweep runner: throughput/worker rate, the
    fairness family (min/max, Jain index, NaN-safe span), completion-
    latency percentiles, and ``energy_pj_per_op`` under ``energy_fit``
    (default: the frozen Table II calibration).

    Degenerate configurations (``n_workers == n_cores`` leaves no atomic
    cores; ``n_workers == 0`` has no workers) consistently report 0.0
    instead of crashing on empty slices.
    """
    return metrics_mod.attach(res, n_workers, cycles, fit=energy_fit)


def execute(p: SimParams, energy_fit=None) -> Dict[str, np.ndarray]:
    """Run one configuration and return the raw metric-annotated result
    dict.  Internal engine entry point: the supported public surface is
    :func:`repro.sync.run`, which wraps this in a typed
    :class:`repro.sync.Result`."""
    with span("repro.run.dispatch", **dispatch_args(p)):
        out = _run(p)
    with span("repro.run.fetch"):
        res = {k: np.asarray(v) for k, v in out.items()}
    with span("repro.run.metrics"):
        return derive_metrics(res, min(p.n_workers, p.n_cores), p.cycles,
                              energy_fit=energy_fit)


def run(p: SimParams, energy_fit=None) -> Dict[str, np.ndarray]:
    """Deprecated legacy entry point — use ``repro.sync.run(Spec(...))``.

    Behaviour is unchanged (bit-identical result dict; the equivalence
    is locked in by ``tests/test_sync_api.py``); only the warning is
    new.
    """
    warnings.warn(
        "repro.core.sim.run() is deprecated; use repro.sync.run(Spec(...))"
        " which returns a typed Result (run().stats carries this dict).",
        DeprecationWarning, stacklevel=2)
    return execute(p, energy_fit=energy_fit)
