"""``nb_feb`` — full/empty-bit atomics as a retry-free universal
primitive (NB-FEB, arXiv:0811.1304).

Every synchronization word carries a hardware **full/empty bit** (FEB).
An acquire is a ``readFE``: when the bit is *full* the word is handed
over and the bit flips to empty in the same bank access — no retry is
ever possible, the bit test and the claim are one atomic port
operation.  When the bit is empty the requester is appended to the
bank-side waiter FIFO and parks clock-gated (the *waiting* NB-FEB
variant: the paper's non-blocking forms return the bit state instead,
but on a manycore the polling-free wait is exactly what LRSCwait
demonstrates, so this plugin models the wait-class member of the same
family).  The release is a ``writeEF``: it stores, then either hands
the word straight to the FIFO head (bit stays empty — ownership moves,
the bit never lies) or sets the bit full when nobody waits.

Compared to ``lrscwait`` this is the capacity-collapse-free universal
form: the FEB is one bit per word and the waiter FIFO is sized for one
outstanding op per core, so there is NO full-queue ``OUT_FAIL`` path at
any core count — ``retry_free`` is part of the declared contract, not a
parameter choice.  The invariant the model checker certifies is that
the bit always tracks the queue: ``feb == (qlen == 0)`` in every
reachable state (the bit is the hardware-visible shadow of "no holder
and no waiters").
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.protocols.base import (NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_GRANT, OUT_NONE, OUT_SLEEP, RESP,
                                       SLEEP, Contract, FifoQueueRecovery,
                                       FusedOut, Protocol, put_cols)
from repro.core.protocols.registry import register


@register
class NbFeb(FifoQueueRecovery, Protocol):
    # single FIFO whose head is the owner (grantees enqueue too), so the
    # stock FIFO watchdog recovery applies; on_timeout only additionally
    # re-derives the bit after an eviction (see below)
    name = "nb_feb"
    uses_queue = True
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=True,
                        max_hot_scatters=4)   # measured 2 (+2 headroom)

    def q_cap(self, p, n):
        # the FIFO holds at most one entry per core (each core has one
        # outstanding op); q_slots does not apply — there is no finite-q
        # variant of a one-bit primitive
        return n

    def wake_delay(self, p):
        return p.lat

    def init_bank_state(self, p, a, n, q_cap):
        return dict(
            feb=jnp.ones((a,), bool),            # full/empty bit: full=free
            qbuf=jnp.full((a, q_cap), -1, jnp.int32),
            qhead=jnp.zeros((a,), jnp.int32),
            qlen=jnp.zeros((a,), jnp.int32),
            wake_tmr=jnp.zeros((a,), jnp.int32),
        )

    def on_access(self, ctx, cs, bank):
        p, wa, q_cap = ctx.p, ctx.wa, ctx.q_cap
        is_acq, is_rel = ctx.is_acq, ctx.is_rel
        acq_b, rel_b, win = ctx.acq_b, ctx.rel_b, ctx.win_core
        feb = bank["feb"]
        qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
        # readFE: bit full -> take the word (bit flips empty); bit empty
        # -> join the waiter FIFO and sleep.  Never fails.
        grant = is_acq & feb[wa]
        enq = is_acq & ~feb[wa]
        # every acquirer enters the FIFO (the grantee at its head), so
        # head == owner and release order is the service order
        put_b = acq_b
        slot_b = (qhead + qlen) % q_cap
        qbuf = qbuf.at[jnp.where(put_b, ctx.ba, ctx.a), slot_b].set(
            win, mode="drop")
        feb = jnp.where(acq_b, False, feb)
        cs["st"] = jnp.where(grant, RESP, jnp.where(enq, SLEEP, cs["st"]))
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        # writeEF: pop the owner; hand off to the new head, or set the
        # bit full when the FIFO drained
        qhead = jnp.where(rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + put_b - rel_b
        cs["st"] = jnp.where(is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(is_rel, NXT_WORK_DONE, cs["nxt"])
        pend_b = rel_b & (qlen > 0)
        feb = jnp.where(rel_b & (qlen == 0), True, feb)
        bank["wake_tmr"] = jnp.where(pend_b, self.wake_delay(p),
                                     bank["wake_tmr"])
        bank["feb"] = feb
        bank["qbuf"], bank["qhead"], bank["qlen"] = qbuf, qhead, qlen
        return cs, bank

    def fused_access(self, fx, bank):
        q_cap = fx.q_cap
        feb = bank["feb"]
        qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
        grant_b = fx.acq_b & feb
        enq_b = fx.acq_b & ~feb
        put_b = fx.acq_b
        slot_b = (qhead + qlen) % q_cap
        qbuf = put_cols(qbuf, slot_b, put_b, fx.win)
        feb = feb & ~fx.acq_b          # (Mosaic lowers no bool constant)
        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(fx.rel_b, OUT_DONE, OUT_NONE))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        qhead = jnp.where(fx.rel_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen + put_b - fx.rel_b
        pend_b = fx.rel_b & (qlen > 0)
        feb = feb | (fx.rel_b & (qlen == 0))
        wake_tmr = jnp.where(pend_b, self.wake_delay(fx.p),
                             bank["wake_tmr"])
        bank = dict(bank, feb=feb, qbuf=qbuf, qhead=qhead, qlen=qlen,
                    wake_tmr=wake_tmr)
        return bank, FusedOut(kind=kind, tmr=tmr)

    def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
        # stock FIFO eviction; evicting the LAST entry must also set the
        # bit full again, or the bank refuses every future readFE — the
        # bit re-derivation IS the certified invariant feb == (qlen==0)
        cs, bank, kind = super().on_timeout(ctx, cs, bank, stuck_b,
                                            killed, owner)
        bank["feb"] = bank["qlen"] == 0
        return cs, bank, kind
