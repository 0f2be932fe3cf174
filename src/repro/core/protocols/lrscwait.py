"""``lrscwait`` — LRwait/SCwait with q reservation slots per bank.

Linearizes contending RMWs at the LR: an LRwait to a non-empty queue
enqueues and the core sleeps (no polling); the SCwait always succeeds and
wakes the next head.  With q ≥ N this is LRSCwait_ideal; an LRwait to a
FULL queue fails immediately and falls back to retry traffic (the
capacity collapse of Fig. 3's ``LRSCwait_q`` lines).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.protocols.base import (NXT_BACKOFF, NXT_MOD, NXT_WORK_DONE,
                                       OUT_DONE, OUT_FAIL, OUT_GRANT,
                                       OUT_NONE, OUT_SLEEP, RESP, SLEEP,
                                       Contract, FifoQueueRecovery, FusedOut,
                                       Protocol, put_cols)
from repro.core.protocols.registry import register


@register
class LrscWait(FifoQueueRecovery, Protocol):
    # the FIFO watchdog recovery applies directly: the queue head IS the
    # reservation owner (grantees enqueue too), so evicting a dead head
    # hands the reservation to the next waiter (repro.faults)
    name = "lrscwait"
    uses_queue = True
    # wait-class: contenders sleep in the bank queue.  OUT_FAIL exists
    # but ONLY at a full queue (the finite-q capacity collapse of
    # Fig. 3) — the model checker verifies every FAIL against its own
    # waiter count.  Grantees enqueue too, so queue_depth counts the
    # holder.
    contract = Contract(exclusive_grant=True, wait_class=True,
                        fail_requires_full=True, queue_counts_holder=True,
                        max_hot_scatters=4)
    #: colibri: SuccessorUpdate on enqueue-behind + WakeUpRequest round trip
    successor_updates = False

    def q_cap(self, p, n):
        return min(p.q_slots, n)

    def wake_delay(self, p):
        return p.lat

    def init_bank_state(self, p, a, n, q_cap):
        return dict(
            qbuf=jnp.full((a, q_cap), -1, jnp.int32),
            qhead=jnp.zeros((a,), jnp.int32),
            qlen=jnp.zeros((a,), jnp.int32),
            wake_tmr=jnp.zeros((a,), jnp.int32),
        )

    def on_access(self, ctx, cs, bank):
        p, wa, q_cap = ctx.p, ctx.wa, ctx.q_cap
        is_acq, is_rel = ctx.is_acq, ctx.is_rel
        acq_b, rel_b, win = ctx.acq_b, ctx.rel_b, ctx.win_core
        qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
        empty = qlen[wa] == 0
        full = qlen[wa] >= q_cap
        grant = is_acq & empty
        enq = is_acq & ~empty & ~full
        rej = is_acq & full                  # finite-q immediate fail
        # bank-side queue updates are dense: at most one winner per bank
        # (either an acquire or a release), so enqueue/dequeue never
        # race within a cycle and the scatters collapse to vector ops
        put_b = acq_b & (qlen < q_cap)
        slot_b = (qhead + qlen) % q_cap
        qbuf = qbuf.at[jnp.where(put_b, ctx.ba, ctx.a), slot_b].set(
            win, mode="drop")
        cs["st"] = jnp.where(grant, RESP, jnp.where(enq, SLEEP, cs["st"]))
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        cs["st"] = jnp.where(rej, RESP, cs["st"])
        cs["tmr"] = jnp.where(rej, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(rej, NXT_BACKOFF, cs["nxt"])
        cs["polls"] = cs["polls"] + rej.sum()
        # colibri SuccessorUpdate traffic on enqueue-behind
        if self.successor_updates:
            cs["msgs"] = cs["msgs"] + 2 * enq.sum()
        # SCwait: always valid (only the head ever gets a response)
        qhead = jnp.where(rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + put_b - rel_b
        cs["st"] = jnp.where(is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(is_rel, NXT_WORK_DONE, cs["nxt"])
        pend_b = rel_b & (qlen > 0)
        bank["wake_tmr"] = jnp.where(pend_b, self.wake_delay(p),
                                     bank["wake_tmr"])
        if self.successor_updates:
            cs["msgs"] = cs["msgs"] + 2 * pend_b.sum()  # WakeUpReq + resp
        bank["qbuf"], bank["qhead"], bank["qlen"] = qbuf, qhead, qlen
        return cs, bank

    def fused_access(self, fx, bank):
        q_cap = fx.q_cap
        qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
        empty_b = qlen == 0
        full_b = qlen >= q_cap
        grant_b = fx.acq_b & empty_b
        enq_b = fx.acq_b & ~empty_b & ~full_b
        rej_b = fx.acq_b & full_b                # finite-q immediate fail
        put_b = fx.acq_b & ~full_b
        slot_b = (qhead + qlen) % q_cap
        qbuf = put_cols(qbuf, slot_b, put_b, fx.win)
        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(rej_b, OUT_FAIL,
                                jnp.where(fx.rel_b, OUT_DONE, OUT_NONE)))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        # SCwait: always valid (only the head ever gets a response)
        qhead = jnp.where(fx.rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + put_b - fx.rel_b
        pend_b = fx.rel_b & (qlen > 0)
        wake_tmr = jnp.where(pend_b, self.wake_delay(fx.p),
                             bank["wake_tmr"])
        msgs = None
        if self.successor_updates:               # SuccUpdate + WakeUpReq RTs
            msgs = 2 * (enq_b.astype(jnp.int32) + pend_b.astype(jnp.int32))
        bank = dict(bank, qbuf=qbuf, qhead=qhead, qlen=qlen,
                    wake_tmr=wake_tmr)
        return bank, FusedOut(kind=kind, tmr=tmr, msgs=msgs)
