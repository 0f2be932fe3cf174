"""Protocol plugin interface for the cycle-level engine (``core.sim``).

The engine owns everything protocol-agnostic: per-core timers and state
transitions, the backoff policy, worker traffic, network acceptance with
head-of-line blocking, and per-bank FIFO arbitration.  A ``Protocol``
owns only what happens when an arbitrated request reaches its bank:

* ``init_bank_state``  — the per-bank pytree (reservation slots, queues,
  lock bits, ...) carried through the ``lax.scan``.
* ``init_core_state``  — optional per-core protocol state (e.g. a ticket).
* ``on_access``        — handle this cycle's bank winners (at most one per
  bank, guaranteed by the engine's arbitration), split into acquire
  (``ctx.is_acq``) and release (``ctx.is_rel``) lanes.
* ``on_wake``          — queue-based protocols: fire wake-up timers and
  move sleeping cores back to their critical section.

Handlers are pure: they take the mutable dicts (``cs`` for per-core state
+ message/poll counters, ``bank`` for bank state) and return updated
copies.  All protocol logic stays inside masked vectorized updates over
the full core/bank arrays — a handler is exactly one of the former
``step()`` branches, lifted into a module.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# core states (BARWAIT: parked at a workload barrier, polling-free)
WORK, REQ, SLEEP, MOD, BACKOFF, RESP, BARWAIT = 0, 1, 2, 3, 4, 5, 6
# request phases
P_ACQ, P_REL = 0, 1
# resp_next codes
NXT_WORK_DONE, NXT_MOD, NXT_BACKOFF = 0, 1, 2

# per-bank outcome codes emitted by the kernel-fusable form
# (``fused_access``): what happens to this bank's winning core.  The
# engine maps them back onto the per-core (st, nxt) writes the masked
# ``on_access`` form performs directly — OUT_GRANT -> RESP/NXT_MOD,
# OUT_DONE -> RESP/NXT_WORK_DONE (and one latency-histogram sample),
# OUT_FAIL -> RESP/NXT_BACKOFF (and one poll), OUT_SLEEP -> SLEEP with
# the timer untouched, OUT_NONE -> no winner / no core-side effect.
OUT_NONE, OUT_GRANT, OUT_DONE, OUT_FAIL, OUT_SLEEP = 0, 1, 2, 3, 4
# recovery outcome codes emitted by ``on_timeout`` (the reservation
# watchdog, repro.faults): OUT_EVICT — a dead owner was evicted and the
# resource handed on; OUT_REDELIVER — a lost wakeup was re-sent to a
# live sleeper.  Both are bank-side events (no per-core apply; the
# evicted core is dead and the redelivered one wakes through the normal
# on_wake path), counted into the ``recoveries`` stat by the engine.
OUT_EVICT, OUT_REDELIVER = 5, 6


@dataclasses.dataclass(frozen=True)
class Contract:
    """Machine-checkable protocol contract, consumed by the static-
    analysis subsystem (``repro.analysis``).

    Every registered protocol declares one.  The model checker
    (``repro.analysis.model_check``) drives the protocol's hooks over
    exhaustive interleavings of tiny configurations and enforces the
    rules the flags enable; the trace auditor
    (``repro.analysis.trace_safety``) enforces the scatter budget.
    These are the paper's claims (polling-freedom, retry-freedom, no
    lost wakeups) stated per protocol as checkable obligations instead
    of repo folklore.
    """
    #: OUT_GRANT (or a wake) hands EXCLUSIVE ownership: at most one
    #: core may hold a bank at any time, and only the holder's release
    #: may complete.  False for bare LR/SC, where every LR is answered
    #: and non-owners only discover failure at the SC.
    exclusive_grant: bool = True
    #: retry-free: OUT_FAIL is unreachable when queues are sized for
    #: the core count (colibri's unbounded queue, amo's single access).
    retry_free: bool = False
    #: wait-class: contenders are parked with OUT_SLEEP and woken by
    #: the protocol (polling-free) instead of polling via OUT_FAIL.
    wait_class: bool = False
    #: OUT_FAIL is legal ONLY when the bank's queue is full — the
    #: lrscwait finite-q capacity collapse.  Checked against the model
    #: checker's independently tracked waiter count.
    fail_requires_full: bool = False
    #: ``on_timeout`` may act on a bank whose owner is LIVE (lrsc's
    #: unconditional reservation expiry is safe by construction: a live
    #: owner just sees its SC fail and retries).  Protocols without
    #: this flag must never return OUT_EVICT for a live owner — that is
    #: the stale-owner class of bug (PR 8).
    evict_live_safe: bool = False
    #: ``queue_depth`` counts the current holder as well as the
    #: sleepers (lrscwait/colibri/mwait grantees enqueue and are popped
    #: at release; colibri_hier grantees bypass the local queues).
    #: Only meaningful for queue protocols.
    queue_counts_holder: bool = True
    #: trace-safety budget: scatter-family ops allowed in the hot scan
    #: body on the reference config (xla_cpu, dense arbitration, no
    #: faults/telemetry/trace).  A regression that reintroduces n-lane
    #: scatters into the hot path fails the audit, not a benchmark.
    max_hot_scatters: int = 0


def mset(arr, idx, mask, val):
    """Masked scatter-set: only lanes with mask write; others dropped
    (out-of-bounds index). Avoids duplicate-index races."""
    oob = jnp.full_like(idx, arr.shape[0])
    return arr.at[jnp.where(mask, idx, oob)].set(val, mode="drop")


# ---- dense gather/scatter stand-ins for ``fused_access`` -------------------
# The TPU kernel compiler (Mosaic) lowers neither scatters nor gathers by a
# computed index, nor a reshape of a bool vector, so the kernel-fusable
# forms restate every indexed read or write as a one-hot select over a
# static iota, with masks folded into the index (-1 selects nothing).  Each
# bank lane reads and writes only its own row(s), so a select picks at most
# one element.

def take_cols(buf, col):
    """``buf[arange(r), col]`` for an ``(r, c)`` buffer: each row's
    element at its own column index."""
    hit = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1) == col[:, None]
    if buf.dtype == jnp.bool_:
        return jnp.sum(jnp.where(hit & buf, 1, 0), axis=1) > 0
    return jnp.sum(jnp.where(hit, buf, 0), axis=1).astype(buf.dtype)


def put_cols(buf, col, mask, val):
    """``buf.at[arange(r), col].set(val)`` on the rows where ``mask``;
    ``val`` is a scalar or one value per row (a Python bool for a bool
    ``buf``: Mosaic lowers no bool constant, so it becomes a bit op)."""
    hit = (jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
           == jnp.where(mask, col, -1)[:, None])
    if buf.dtype == jnp.bool_:
        return (buf | hit) if val else (buf & ~hit)
    val = jnp.asarray(val, buf.dtype)
    return jnp.where(hit, val[:, None] if val.ndim else val, buf)


# The same two for state stored with banks on the last (lane) axis, an
# ``(r, a)`` buffer whose column ``b`` is bank ``b``'s ``r`` entries: each
# lane reads or writes its own column at its own row index.

def take_lanes(buf, row):
    """``buf[row, arange(c)]`` for an ``(r, c)`` buffer: each column's
    element at its own row index."""
    hit = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0) == row[None, :]
    if buf.dtype == jnp.bool_:
        return jnp.sum(jnp.where(hit & buf, 1, 0), axis=0) > 0
    return jnp.sum(jnp.where(hit, buf, 0), axis=0).astype(buf.dtype)


def put_lanes(buf, row, mask, val):
    """``buf.at[row, arange(c)].set(val)`` on the columns where ``mask``;
    ``val`` as for :func:`put_cols`."""
    row = jnp.where(mask, row, -1)
    if buf.dtype != jnp.bool_ and jnp.ndim(val):
        # row and value reach the lanes as one (2, c) array: Mosaic lays
        # them out together, where a value broadcast on its own added
        # ~3.5 s to the kernel's compile at a 256-bank tile (AOT, v5e)
        rv = jnp.stack([row, jnp.asarray(val, jnp.int32)])
        row, val = rv[0:1], rv[1:2].astype(buf.dtype)
    else:
        row = row[None, :]
    hit = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0) == row
    if buf.dtype == jnp.bool_:
        return (buf | hit) if val else (buf & ~hit)
    return jnp.where(hit, jnp.asarray(val, buf.dtype), buf)


# ---- bank-to-core delivery ------------------------------------------------
# A bank's result of a cycle belongs to one core, and that core targets
# the bank: a bank serves only a core whose target ``wa`` is that bank, and
# a core sleeps only in a queue of the bank it asked.  So "bank b sends x to
# core c" is also "core c reads x from its own bank, if that bank names
# c".  The engine picks the direction from the static shape
# (``core.sim.bank_to_core``, handed on as ``Ctx.to_cores``):
# ``"scatter"`` writes each bank's result onto its core, one index per
# bank; ``"gather"`` has each core read its own bank, one index per core,
# and is the cheaper one when there are few cores per bank.  Both give
# the same bits.

def at_cores(wa, *rows):
    """The gather form's read: each core's values of the per-bank
    ``rows`` at its own bank ``wa``, one gather of the rows stacked
    (int32 or bool rows; each keeps its dtype)."""
    got = jnp.stack([r.astype(jnp.int32) for r in rows], axis=1)[wa]
    return tuple(got[:, i].astype(r.dtype) for i, r in enumerate(rows))


def fired(form, fire_core, wc):
    """(n,) bool: the cores their banks fire.  ``fire_core`` is the core
    each bank fires (``n``: none): per bank on the scatter form, which
    scatters it onto the cores; on the gather form per core, as read at
    its own bank (:func:`at_cores`), and a core is fired iff it is named."""
    if form == "gather":
        return fire_core == wc
    return jnp.zeros(wc.shape, bool).at[fire_core].set(True, mode="drop")


def onehot_rows(idx, mask, size):
    """``(len(idx), size)`` bool: lane ``i`` selects row ``idx[i]`` of a
    length-``size`` array when ``mask[i]`` (distinct lanes must select
    distinct rows)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], size), 1)
    return rows == jnp.where(mask, idx, -1)[:, None]


def take_rows(x, idx):
    """``x[idx]`` for a 1-D int ``x``."""
    hit = (jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], x.shape[0]), 1)
           == idx[:, None])
    return jnp.sum(jnp.where(hit, x[None, :], 0), axis=1).astype(x.dtype)


def scatter_rows(hit, val):
    """Per-row value a one-hot ``hit`` (from :func:`onehot_rows`) sends:
    ``val[i]`` at row ``idx[i]``, 0 on rows no lane selects."""
    return jnp.sum(jnp.where(hit, val[:, None], 0), axis=0)


@dataclasses.dataclass
class Ctx:
    """Per-cycle view handed to protocol handlers.

    ``p`` is the resolved parameter namespace — fields may be traced
    scalars when running under the vmapped sweep (``core.sweep``), so
    handlers must treat them as jax values, never as Python ints for
    shapes.  ``n``/``a``/``q_cap`` are always static.
    """
    p: Any                   # resolved SimParams-like namespace
    n: int                   # cores (static)
    a: int                   # banks allocated (static upper bound)
    q_cap: int               # queue slots per bank (static)
    is_acq: jnp.ndarray      # (n,) bool — this cycle's acquire winners
    is_rel: jnp.ndarray      # (n,) bool — this cycle's release winners
    wa: jnp.ndarray          # (n,) int32 — each core's target bank
    wc: jnp.ndarray          # (n,) int32 — arange(n) core ids
    ba: jnp.ndarray = None   # (a,) int32 — arange(a) bank ids (hoisted
    #                          once per trace; handlers reuse it instead
    #                          of building a fresh iota every cycle)
    #: (a,) int32 — each bank's winning core this cycle, or ``n`` when
    #: the bank has no winner.  The engine guarantees at most one winner
    #: per bank, so protocols can update bank-side state *densely* —
    #: ``jnp.where(acq_b, f(win_core), state)`` — instead of scattering
    #: n core lanes into a-sized arrays; that turns every bank-state
    #: write from an n-lane scatter into O(a) vector ops (the dominant
    #: cost of queue protocols on CPU).  Gathering core-side values at
    #: ``jnp.minimum(win_core, n - 1)`` is safe; mask with acq_b/rel_b.
    win_core: jnp.ndarray = None
    acq_b: jnp.ndarray = None   # (a,) bool — bank winner is an acquire
    rel_b: jnp.ndarray = None   # (a,) bool — bank winner is a release
    #: (n,) int32 — each core's *current micro-op* modify duration.  The
    #: engine interprets workload programs (``core.workloads``), so the
    #: cycles between load and store are a per-step property, not the
    #: global ``p.modify``; wake paths must grant with this value.
    mod_dur: jnp.ndarray = None
    #: how per-bank results reach the core lanes, ``"scatter"`` or
    #: ``"gather"`` (static; see :func:`at_cores` and :func:`fired`)
    to_cores: str = "scatter"


@dataclasses.dataclass
class FusedCtx:
    """Bank-centric view handed to :meth:`Protocol.fused_access` — the
    kernel-fusable twin of :class:`Ctx`.

    Everything is **block-local and dense over banks**: the arrays are
    ``(a,)``-shaped for the bank block being processed (the whole bank
    range on the reference path, one tile of it inside the Pallas
    ``engine_step`` kernel), and there are NO ``(n,)``-shaped core
    arrays to write — per-core effects are *returned* as outcome codes
    and scattered by the engine.  A conforming ``fused_access``:

    * reads/writes bank state arrays sliced to this block (every bank
      array's leading dim is ``m * a`` for some per-protocol ``m``, or,
      where the protocol sets :attr:`Protocol.bank_last`, its last dim
      is ``a``, so blocks slice cleanly);
    * indexes banks with a **local** iota (``jnp.arange(a)``), never a
      global bank id;
    * touches per-core state only through ``core`` (values the engine
      gathered at the winning core) and the returned ``xset`` writes;
    * treats ``p`` fields as possibly-traced scalars (inside the kernel
      they arrive through the scalar operand, not a Python closure).
    """
    p: Any                   # resolved params namespace (lat, ... traced ok)
    n: int                   # cores (static)
    a: int                   # banks in THIS block (static)
    q_cap: int               # queue slots per bank (static)
    win: jnp.ndarray         # (a,) int32 winning core id, or n if none
    acq_b: jnp.ndarray       # (a,) bool — winner is an acquire
    rel_b: jnp.ndarray       # (a,) bool — winner is a release
    #: per-core values gathered at ``min(win, n-1)`` for the fields the
    #: protocol listed in ``fused_core_fields`` (mask with acq_b/rel_b)
    core: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class FusedOut:
    """Per-bank outputs of :meth:`Protocol.fused_access`.

    ``kind`` drives the engine's generic core-side apply (see the
    ``OUT_*`` codes); ``tmr`` is the response timer for the RESP-kind
    outcomes (``OUT_GRANT``/``OUT_DONE``/``OUT_FAIL``); ``msgs`` counts
    protocol side-messages beyond the engine's 2-per-winner; ``xset``
    maps a per-core state field name to ``(values, mask)`` pairs the
    engine scatters to the winning cores (e.g. the ticket lock's drawn
    ticket).  Polls are derived: every ``OUT_FAIL`` is one poll.
    """
    kind: jnp.ndarray        # (a,) int32 OUT_* code
    tmr: jnp.ndarray         # (a,) int32 response timer for RESP kinds
    msgs: jnp.ndarray = None          # (a,) int32 extra messages (or None)
    xset: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = \
        dataclasses.field(default_factory=dict)


class Protocol:
    """Base protocol plugin. Subclasses override the hooks they need."""

    name: str = ""
    #: machine-checkable contract (see :class:`Contract`) enforced by
    #: ``python -m repro.analysis``; subclasses override.
    contract: Contract = Contract()
    #: queue-based protocols get the engine's wake pass and their wake-up
    #: responses counted against next cycle's network budget.
    uses_queue: bool = False
    #: lock-style protocols use the paper's FIXED backoff (exp cap 1);
    #: bare retry protocols use the calibrated exponential policy.
    fixed_backoff: bool = False
    #: per-core state fields ``fused_access`` needs gathered at the
    #: winning core (handed back as ``FusedCtx.core``)
    fused_core_fields: Tuple[str, ...] = ()
    #: per-core state fields ``fused_access`` may write via
    #: ``FusedOut.xset`` (static: sizes the kernel's output pytree)
    fused_xset_fields: Tuple[str, ...] = ()
    #: bank-state layout: ``False``, every bank array's leading dim is
    #: ``m * a``; ``True``, every bank array is ``(a,)`` or ``(r, a)``,
    #: the bank on the last (lane) axis (the engine-step kernel tiles it
    #: ``(r, block_a)``)
    bank_last: bool = False

    # ---- static sizing ----
    def q_cap(self, p, n: int) -> int:
        """Queue slots per bank (static). Default: one per core."""
        return n

    # ---- state ----
    def init_bank_state(self, p, a: int, n: int, q_cap: int) -> Dict:
        return {}

    def queue_depth(self, bank: Dict):
        """(a,) per-bank reservation-queue occupancy, or ``None`` for
        queueless protocols — the engine's telemetry/trace layers
        (``repro.obs``) read it once per cycle.  Default: the single
        FIFO queue's ``qlen``; hierarchical protocols override to sum
        their per-bank lanes."""
        return bank.get("qlen")

    def result_bank(self, bank: Dict) -> Dict:
        """The final bank state as ``Result.stats`` carries it.  Default:
        as stored; a protocol that stores state in a layout made for the
        kernel (:attr:`bank_last`) maps it back to its documented one."""
        return bank

    def init_core_state(self, p, n: int) -> Dict:
        return {}

    # ---- handlers ----
    def on_access(self, ctx: Ctx, cs: Dict, bank: Dict
                  ) -> Tuple[Dict, Dict]:
        raise NotImplementedError

    def fused_access(self, fx: FusedCtx, bank: Dict
                     ) -> Tuple[Dict, FusedOut]:
        """Kernel-fusable dense bank update: the bank-state side of
        :meth:`on_access`, restated so the Pallas ``engine_step`` kernel
        (``repro.kernels.engine_step``) can trace it over one bank tile
        — block-local, dense over banks, per-core effects returned as
        ``OUT_*`` outcome codes instead of written.  Must be
        behaviourally identical to ``on_access`` + the engine's generic
        outcome apply; ``tests/test_engine_backend.py`` pins the two
        paths bit-identical across the full protocol × workload grid.
        """
        raise NotImplementedError(
            f"protocol {self.name!r} does not provide the kernel-fusable "
            f"fused_access form required by the pallas backends")

    def on_wake(self, ctx: Ctx, cs: Dict, bank: Dict
                ) -> Tuple[Dict, Dict, jnp.ndarray]:
        """Fire wake-up timers; return (cs, bank, wake_load) where
        ``wake_load`` is the number of wake responses that will occupy
        network slots next cycle.  Default implementation: a single FIFO
        queue per bank (lrscwait / colibri / mwait_lock)."""
        wake_tmr = bank["wake_tmr"]
        fire = wake_tmr == 1
        wake_tmr = jnp.maximum(wake_tmr - 1, 0)
        ba = ctx.ba if ctx.ba is not None else jnp.arange(ctx.a)
        # wake the head core of each firing queue
        if ctx.to_cores == "gather":
            # each core reads the head of its own bank's queue
            head, live = at_cores(ctx.wa, bank["qhead"],
                                  fire & (bank["qlen"] > 0))
            fire_core = jnp.where(live, bank["qbuf"][ctx.wa, head], ctx.n)
        else:
            head_core = bank["qbuf"][ba, bank["qhead"]]
            fire_core = jnp.where(fire & (bank["qlen"] > 0), head_core,
                                  ctx.n)
        woken = fired(ctx.to_cores, fire_core, ctx.wc)
        cs["st"] = jnp.where(woken, MOD, cs["st"])
        cs["tmr"] = jnp.where(woken, ctx.mod_dur, cs["tmr"])
        bank["wake_tmr"] = wake_tmr
        return cs, bank, (wake_tmr == 1).sum()

    # ---- fault recovery (repro.faults) ----------------------------------
    def held(self, bank: Dict):
        """(a,) bool — which banks are currently *held* (a reservation,
        lock or turn is outstanding, so a dead owner wedges the bank).
        ``None`` (the default) means the protocol has no held state and
        can never get stuck — the engine then skips the watchdog
        entirely (amo: every access commits at the bank)."""
        return None

    def on_timeout(self, ctx: Ctx, cs: Dict, bank: Dict,
                   stuck_b: jnp.ndarray, killed: jnp.ndarray,
                   owner: jnp.ndarray) -> Tuple[Dict, Dict, jnp.ndarray]:
        """Reservation-watchdog recovery: called once per cycle (only
        when the plan arms ``watchdog_cyc``) with ``stuck_b`` (a,) —
        banks held with no service progress for ``watchdog_cyc`` cycles
        — the permanent-kill mask ``killed`` (n,) and the engine-tracked
        last grantee ``owner`` (a,; ``n`` = unknown).  Returns
        ``(cs, bank, kind)`` with ``kind`` (a,) an OUT_EVICT /
        OUT_REDELIVER / OUT_NONE code per bank.  Default: no recovery
        (the watchdog observes but cannot act)."""
        return cs, bank, jnp.zeros((ctx.a,), jnp.int32)


class FifoQueueRecovery:
    """``on_timeout`` for the single-FIFO sleep protocols (lrscwait /
    colibri / mwait_lock), where the queue head IS the current owner:
    a stuck bank whose head core is permanently dead is evicted (head
    advances; the reservation passes to the next waiter via a normal
    wake), and a stuck bank whose head is alive but asleep had its
    wakeup lost — re-send it.  Mixin over :class:`Protocol` subclasses
    exposing ``qbuf``/``qhead``/``qlen``/``wake_tmr`` bank state and a
    ``wake_delay(p)`` policy."""

    def held(self, bank):
        return bank["qlen"] > 0

    def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
        q_cap, n = ctx.q_cap, ctx.n
        qhead, qlen = bank["qhead"], bank["qlen"]
        head = bank["qbuf"][ctx.ba, qhead]
        head_dead = (head >= 0) & killed[jnp.clip(head, 0, n - 1)]
        evict_b = stuck_b & head_dead
        qhead = jnp.where(evict_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen - evict_b
        redeliver_b = stuck_b & ~head_dead
        # hand the reservation to the new head / re-send the lost wake
        wake_b = (evict_b | redeliver_b) & (qlen > 0)
        bank["wake_tmr"] = jnp.where(wake_b, self.wake_delay(ctx.p),
                                     bank["wake_tmr"])
        cs["msgs"] = cs["msgs"] + 2 * wake_b.sum()   # wake round trip
        bank.update(qhead=qhead, qlen=qlen)
        kind = jnp.where(evict_b, OUT_EVICT,
                         jnp.where(redeliver_b & wake_b, OUT_REDELIVER,
                                   OUT_NONE)).astype(jnp.int32)
        return cs, bank, kind
