"""``mwait_lock`` — MCS queue lock where waiters sleep via Mwait.

Contenders enqueue at the bank and sleep (Mwait setup costs messages);
the releaser wakes its successor directly — polling-free, but every
critical section pays lock-management round trips that the direct
LRSCwait RMW avoids.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.protocols.base import (NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_GRANT, OUT_NONE, OUT_SLEEP, RESP,
                                       SLEEP, Contract, FifoQueueRecovery,
                                       FusedOut, Protocol, put_cols)
from repro.core.protocols.registry import register


@register
class MwaitLock(FifoQueueRecovery, Protocol):
    # same queue shape as lrscwait (head = lock holder), so the FIFO
    # watchdog recovery applies: evict a dead holder, wake the successor
    name = "mwait_lock"
    uses_queue = True
    fixed_backoff = True
    # MCS-style queue sized one slot per core: contenders always park,
    # never poll — fully retry-free; the holder stays at the queue head
    # until its release pops it
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=True,
                        max_hot_scatters=4)

    def wake_delay(self, p):
        # successor wake: one response latency + Qnode bounce (the same
        # cost the release-path wake pays)
        return p.lat + 2

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
        grant = is_acq & empty
        enq = is_acq & ~empty
        # dense bank-side queue updates (≤1 winner per bank — see base)
        slot_b = (qhead + qlen) % q_cap
        qbuf = qbuf.at[jnp.where(acq_b, ctx.ba, ctx.a), slot_b].set(
            win, mode="drop")
        cs["st"] = jnp.where(grant, RESP, jnp.where(enq, SLEEP, cs["st"]))
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        cs["msgs"] = cs["msgs"] + 2 * enq.sum()          # Mwait setup
        qhead = jnp.where(rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + acq_b - rel_b
        cs["st"] = jnp.where(is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(is_rel, NXT_WORK_DONE, cs["nxt"])
        pend_b = rel_b & (qlen > 0)
        # releaser wakes the successor: one response latency + Qnode bounce
        bank["wake_tmr"] = jnp.where(pend_b, p.lat + 2, bank["wake_tmr"])
        bank["qbuf"], bank["qhead"], bank["qlen"] = qbuf, qhead, qlen
        return cs, bank

    def fused_access(self, fx, bank):
        q_cap = fx.q_cap
        qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
        empty_b = qlen == 0
        grant_b = fx.acq_b & empty_b
        enq_b = fx.acq_b & ~empty_b
        slot_b = (qhead + qlen) % q_cap
        qbuf = put_cols(qbuf, slot_b, fx.acq_b, fx.win)
        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(fx.rel_b, OUT_DONE, OUT_NONE))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        msgs = 2 * enq_b.astype(jnp.int32)               # Mwait setup
        qhead = jnp.where(fx.rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + fx.acq_b - fx.rel_b
        pend_b = fx.rel_b & (qlen > 0)
        wake_tmr = jnp.where(pend_b, fx.p.lat + 2, bank["wake_tmr"])
        bank = dict(bank, qbuf=qbuf, qhead=qhead, qlen=qlen,
                    wake_tmr=wake_tmr)
        return bank, FusedOut(kind=kind, tmr=tmr, msgs=msgs)
