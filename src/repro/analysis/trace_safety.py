"""Trace-safety auditor: jaxpr-level lint of the engine's single scan.

The engine's performance story rests on *static elision*: optional
features (windowed telemetry, fault injection, the event trace) are
Python-gated so that switched off they change NOTHING about the traced
computation — same single ``lax.scan``, same carry count, same outputs
(the PR 4 "carry cliff" lesson: one stray carry is a compile cliff).
Until now that discipline lived in hand-rolled jaxpr assertions inside
``tests/test_telemetry.py`` / ``tests/test_faults.py``.  This module is
the single implementation those tests (and the CI gate) call:

* ``scan_carry_count(p)`` — the actual ``num_carry`` of the engine's
  hot scan for ``SimParams`` ``p`` (asserting there IS exactly one);
* ``expected_scan_carries(p)`` — the budgeted count of WRITTEN
  carries: the frozen 27-entry engine carry contract
  (:data:`ENGINE_CARRY_KEYS`) + the protocol's bank/core state leaves +
  the feature deltas (+1 telemetry, +3 faults, +2 holder-kill mode, +3
  watchdog, +1 hierarchical topology), less the leaves this config
  never writes (:func:`read_only_carries`) — ``lax.scan`` moves a carry
  its body passes through unchanged out of the loop as a constant;
* ``scatter_count(p)`` — scatter-family ops inside the scan body,
  checked against each protocol's ``contract.max_hot_scatters`` budget
  (a regression reintroducing n-lane scatters into the hot path fails
  the audit, not a benchmark);
* ``audit_protocol(name)`` — the full rule set over one protocol's
  reference configs, including backend parity of the jaxpr-visible
  output structure between ``xla_cpu`` and ``pallas_interpret``.

Rules: ``single-scan``, ``carry-count``, ``ys-count``,
``scatter-budget``, ``backend-parity``, ``static-knob``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding, PassReport
from repro.core import sim
from repro.core import sweep
from repro.core.protocols import registry as proto_registry
from repro.core.protocols.base import Ctx
from repro.core.topologies import registry as topo_registry
from repro.faults import FaultPlan

#: The engine's fixed carry contract: the top-level keys of the scan
#: state dict in ``core.sim.simulate`` that exist on EVERY config,
#: before protocol state and feature deltas.  Frozen here so a carry
#: regression is a named diff, not a bare count mismatch.
ENGINE_CARRY_KEYS: Tuple[str, ...] = (
    "st", "tmr", "addr", "phase", "pc", "bar_cnt", "nxt", "arr_cyc",
    "parked", "resp_prev", "opc", "streak", "ops", "acq_start",
    "msgs", "polls", "addr_ops", "sleep_cyc", "bar_cyc", "lat_hist",
    "lat_max", "backoff_cyc", "active_cyc", "bank_ops", "net_stall",
    "w_tmr", "w_served")

#: feature deltas (leaves added to the scan carry when the knob is on)
TELEMETRY_CARRIES = 1            # tele accumulator
FAULTS_CARRIES = 3               # faults_injected, halt_cyc, last_ret
HOLDER_KILL_CARRIES = 2          # kmask, kleft
WATCHDOG_CARRIES = 3             # wd_srv, wd_own, recoveries
TOPO_CARRIES = 1                 # hops counter (hierarchical topologies)

#: the Fig. 5 worker carries, which a worker-free trace never writes
WORKER_CARRY_KEYS: Tuple[str, ...] = ("w_tmr", "w_served")

#: ys stacked per cycle when record_trace is on (step/wait/state/qlen)
TRACE_YS = 4

#: SimParams fields that change the traced computation (shapes, carry
#: structure, or the scan body itself) and therefore MUST be static
#: sweep axes — ``core.sweep`` re-traces per combination of these.
CARRY_AFFECTING_FIELDS: Tuple[str, ...] = (
    "protocol", "workload", "n_cores", "cycles", "q_slots", "n_groups",
    "record_trace", "unroll", "backend", "telemetry_windows", "faults",
    "topology", "clusters")


# ---- jaxpr plumbing -----------------------------------------------------
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def engine_jaxpr(p: sim.SimParams):
    """Top-level jaxpr of one engine run of ``p``."""
    return jax.make_jaxpr(lambda: sim.simulate(p))()


def scan_eqns(p: sim.SimParams) -> List[Any]:
    return [e for e in _walk_eqns(engine_jaxpr(p).jaxpr)
            if e.primitive.name == "scan"]


def scan_carry_count(p: sim.SimParams) -> int:
    """``num_carry`` of the engine's hot scan.  Raises if the engine no
    longer traces to exactly one scan — that is itself the regression
    the auditor exists to catch, so callers treating this as a plain
    counter still fail loudly."""
    eqns = scan_eqns(p)
    if len(eqns) != 1:
        raise AssertionError(
            f"engine traced to {len(eqns)} scans (expected exactly 1) "
            f"for {p.protocol}")
    return int(eqns[0].params["num_carry"])


def _scan_handler_writes_polls(p: sim.SimParams) -> bool:
    """Does the protocol's scan-path handler (``on_access``) ever update
    the poll counter?  Probed by tracing the handler alone over one
    all-idle cycle: an untouched counter comes back as the very input."""
    proto = proto_registry.get(p.protocol)
    n, a = p.n_cores, p.n_addrs
    q_cap = proto.q_cap(p, n)
    written = []

    def probe(polls):
        def i32(*s):
            return jnp.zeros(s, jnp.int32)

        def off(*s):
            return jnp.zeros(s, bool)

        ctx = Ctx(
            p=sim._resolve(p), n=n, a=a, q_cap=q_cap, is_acq=off(n),
            is_rel=off(n), wa=i32(n), wc=jnp.arange(n, dtype=jnp.int32),
            ba=jnp.arange(a, dtype=jnp.int32), win_core=i32(a),
            acq_b=off(a), rel_b=off(a), mod_dur=i32(n))
        cs = dict(st=i32(n), tmr=i32(n), nxt=i32(n), polls=polls,
                  msgs=i32(), **proto.init_core_state(p, n))
        cs, _ = proto.on_access(ctx, cs,
                                proto.init_bank_state(p, a, n, q_cap))
        written.append(cs["polls"] is not polls)
        return cs["polls"]

    jax.make_jaxpr(probe)(jnp.zeros((), jnp.int32))
    return written[0]


def read_only_carries(p: sim.SimParams) -> Tuple[str, ...]:
    """Contract leaves the scan body of ``p`` passes through unchanged:
    the worker pair on a worker-free trace (the engine statically elides
    their updates), and ``polls`` when the protocol's handler never
    polls.  ``lax.scan`` hoists such leaves out of the loop, so they are
    not carries of the traced scan."""
    ro = WORKER_CARRY_KEYS if p.n_workers == 0 else ()
    if not _scan_handler_writes_polls(p):
        ro += ("polls",)
    return ro


def expected_scan_carries(p: sim.SimParams) -> int:
    """The written-carry budget for ``p`` from the frozen engine
    contract plus the protocol's declared state and the feature gates,
    less :func:`read_only_carries` — computed WITHOUT tracing the
    engine, so a drift between this formula and the real scan (a stray
    written carry, or a contract key gone missing) is always a
    reportable finding."""
    proto = proto_registry.get(p.protocol)
    n, a = p.n_cores, p.n_addrs
    q_cap = proto.q_cap(p, n)
    bank = proto.init_bank_state(p, a, n, q_cap)
    xc = proto.init_core_state(p, n)
    cnt = (len(ENGINE_CARRY_KEYS)
           + len(jax.tree_util.tree_leaves(bank))
           + len(jax.tree_util.tree_leaves(xc)))
    if p.telemetry_windows > 0:
        cnt += TELEMETRY_CARRIES
    if topo_registry.get(p.topology).levels:     # hierarchical: hops carry
        cnt += TOPO_CARRIES
    fp = p.faults
    if fp.enabled:
        cnt += FAULTS_CARRIES
        if fp.n_kill > 0 and fp.kill_holder == 1:
            cnt += HOLDER_KILL_CARRIES
        if fp.watchdog_cyc > 0 and proto.held(bank) is not None:
            cnt += WATCHDOG_CARRIES
    return cnt - len(read_only_carries(p))


_SCATTER_PREFIX = "scatter"


def scatter_count(p: sim.SimParams) -> int:
    """Scatter-family ops inside the hot scan body (recursing into
    nested jaxprs)."""
    eqns = scan_eqns(p)
    if len(eqns) != 1:
        raise AssertionError(f"expected exactly 1 scan, got {len(eqns)}")
    body = eqns[0].params["jaxpr"].jaxpr
    return sum(1 for e in _walk_eqns(body)
               if e.primitive.name.startswith(_SCATTER_PREFIX))


def _out_struct(p: sim.SimParams):
    return jax.eval_shape(lambda: sim.simulate(p))


# ---- the audit ----------------------------------------------------------
def reference_params(name: str, **kw: Any) -> sim.SimParams:
    """The auditor's reference config: small, dense-arbitration, CPU
    backend, all optional features off (overridable via ``kw``)."""
    base = dict(protocol=name, n_cores=16, cycles=400, n_addrs=4,
                backend="xla_cpu")
    base.update(kw)
    return sim.SimParams(**base)


def _variants(name: str) -> List[Tuple[str, sim.SimParams]]:
    return [
        ("base", reference_params(name)),
        ("telemetry", reference_params(name, telemetry_windows=8)),
        ("trace", reference_params(name, record_trace=True)),
        ("kill", reference_params(
            name, faults=FaultPlan(n_kill=1, kill_cyc=100))),
        ("kill+wd", reference_params(
            name, faults=FaultPlan(n_kill=1, kill_cyc=100,
                                   watchdog_cyc=200))),
        ("cluster2", reference_params(name, topology="cluster2",
                                      clusters=2)),
    ]


def audit_protocol(name: str, quick: bool = False,
                   backend_parity: bool = True) -> PassReport:
    """Audit one protocol: carry budget across the feature variants,
    ys count, single-scan shape, scatter budget, backend parity."""
    rep = PassReport(pass_name="trace", subject=name)
    t0 = time.perf_counter()
    proto = proto_registry.get(name)
    variants = _variants(name)[:1 if quick else None]
    carries: Dict[str, int] = {}
    for label, p in variants:
        eqns = scan_eqns(p)
        if len(eqns) != 1:
            rep.findings.append(Finding(
                "trace", "single-scan", name,
                f"{len(eqns)} scan ops traced (hot loop must be ONE "
                f"scan)", where=label))
            continue
        eqn = eqns[0]
        actual = int(eqn.params["num_carry"])
        expect = expected_scan_carries(p)
        carries[label] = actual
        if actual != expect:
            rep.findings.append(Finding(
                "trace", "carry-count", name,
                f"scan carries {actual} != budget {expect} (engine "
                f"contract {len(ENGINE_CARRY_KEYS)} + protocol state "
                f"+ feature deltas) — a stray carry is a compile "
                f"cliff", where=label))
        ys = len(eqn.outvars) - actual
        ys_expect = TRACE_YS if p.record_trace else 0
        if ys != ys_expect:
            rep.findings.append(Finding(
                "trace", "ys-count", name,
                f"scan stacks {ys} per-cycle outputs, expected "
                f"{ys_expect}", where=label))
    # scatter budget on the reference config
    budget = proto.contract.max_hot_scatters
    nsc = scatter_count(reference_params(name))
    rep.stats["hot_scatters"] = nsc
    rep.stats["scatter_budget"] = budget
    rep.stats["carries"] = carries
    if nsc > budget:
        rep.findings.append(Finding(
            "trace", "scatter-budget", name,
            f"{nsc} scatter ops in the hot scan body exceed the "
            f"contract budget of {budget}", where="base"))
    # backend parity: jaxpr-visible output structure must match across
    # the scan oracle and the Pallas kernel path
    if backend_parity and not quick:
        px = reference_params(name, backend="xla_cpu")
        pi = reference_params(name, backend="pallas_interpret")
        sx, si = _out_struct(px), _out_struct(pi)
        if jax.tree_util.tree_structure(sx) != \
                jax.tree_util.tree_structure(si):
            rep.findings.append(Finding(
                "trace", "backend-parity", name,
                "output tree structure differs between xla_cpu and "
                "pallas_interpret", where="base"))
        else:
            bad = [k for k in sx
                   if (sx[k].shape, sx[k].dtype)
                   != (si[k].shape, si[k].dtype)]
            if bad:
                rep.findings.append(Finding(
                    "trace", "backend-parity", name,
                    f"output avals differ across backends for {bad}",
                    where="base"))
    rep.wall_s = time.perf_counter() - t0
    return rep


def audit_static_fields() -> PassReport:
    """Every carry-affecting knob must be a static sweep axis: a knob
    that re-shapes the jaxpr but rides a dynamic sweep axis would
    silently produce wrong (shape-mismatched or retraced-per-point)
    sweeps."""
    rep = PassReport(pass_name="trace", subject="sweep.STATIC_FIELDS")
    t0 = time.perf_counter()
    missing = [f for f in CARRY_AFFECTING_FIELDS
               if f not in sweep.STATIC_FIELDS]
    if missing:
        rep.findings.append(Finding(
            "trace", "static-knob", "sweep.STATIC_FIELDS",
            f"carry-affecting SimParams fields {missing} are not "
            f"declared static sweep axes"))
    rep.stats["static_fields"] = list(sweep.STATIC_FIELDS)
    rep.wall_s = time.perf_counter() - t0
    return rep


def check_all(quick: bool = False,
              protocols: Optional[List[str]] = None) -> List[PassReport]:
    names = protocols or proto_registry.names()
    reps = [audit_protocol(nm, quick=quick) for nm in names]
    reps.append(audit_static_fields())
    return reps
