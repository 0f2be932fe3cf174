"""``colibri_hier`` — two-level Colibri: group-local queues + a global
spillover queue of groups.

Models the paper's distributed reservations at cluster granularity: cores
are partitioned into ``n_groups`` clusters.  Waiters enqueue in a queue
local to their (address, group) pair — a SuccessorUpdate that stays inside
the cluster (1 hop) and a wake-up that costs only an intra-cluster Qnode
bounce (2 cycles).  A group with waiters registers once in the address's
global FIFO of groups; when the serving group's local queue drains, the
release hands the address to the next registered group with the full
cross-cluster wake round trip (``lat + 2``).

Like flat Colibri this is polling-free and retry-free (local queues are
sized for the worst case of one outstanding RMW per core, so an LRwait
never bounces); unlike flat Colibri, the common-case wake and
SuccessorUpdate stay inside a cluster, trading strict global FIFO for
group-batched service.  Fairness across groups is preserved by a turn
budget: after ``group_size`` ops a group with registered competitors
re-registers at the global tail and hands the address over, so no group
can starve another (round-robin at cluster granularity).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.protocols.base import (MOD, NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_EVICT, OUT_GRANT, OUT_NONE,
                                       OUT_REDELIVER, OUT_SLEEP, RESP, SLEEP,
                                       Contract, FusedOut, Protocol,
                                       at_cores, fired, put_lanes,
                                       take_lanes)
from repro.core.protocols.registry import register


@register
class ColibriHier(Protocol):
    name = "colibri_hier"
    uses_queue = True
    bank_last = True         # queue state (G, a): see init_bank_state
    local_delay = 2          # intra-cluster Qnode bounce
    # retry-free wait-class like flat colibri, but grantees bypass the
    # local queues (woken heads are popped), so queue_depth counts the
    # sleepers ONLY — the conservation rule the PR 6 wake_grp aliasing
    # bug violated
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=False,
                        max_hot_scatters=3)   # measured 1 (+2 headroom)

    @staticmethod
    def _geom(p, n):
        """(n_groups, group_size, local queue capacity) — all static."""
        g = max(1, min(p.n_groups, n))
        gsz = max(1, n // g)
        cap_l = max(gsz, n - (g - 1) * gsz)  # last group may be larger
        return g, gsz, cap_l

    def init_bank_state(self, p, a, n, q_cap):
        # banks on the last (lane) axis, the group (and the local queue's
        # slot) on the leading one: a bank lane reads and writes its own
        # group's queue by a select over the G rows of its own column
        g, _, cap_l = self._geom(p, n)
        return dict(
            lqbuf=jnp.full((g * cap_l, a), -1, jnp.int32),  # row g*cap_l+slot
            lqhead=jnp.zeros((g, a), jnp.int32),
            lqlen=jnp.zeros((g, a), jnp.int32),
            ggq=jnp.full((g, a), -1, jnp.int32),    # FIFO of group ids
            gqhead=jnp.zeros((a,), jnp.int32),
            gqlen=jnp.zeros((a,), jnp.int32),
            g_inq=jnp.zeros((g, a), bool),
            cur_grp=jnp.full((a,), -1, jnp.int32),  # group holding the turn
            turn_srv=jnp.zeros((a,), jnp.int32),    # ops served this turn
            wake_tmr=jnp.zeros((a,), jnp.int32),
            # GROUP whose local queue to wake: a group id, meaningful on
            # any bank tile, as every queue array's column is its bank
            wake_grp=jnp.zeros((a,), jnp.int32),
        )

    def queue_depth(self, bank):
        # total waiters per bank = its G group-local queues summed
        return bank["lqlen"].sum(axis=0)

    def result_bank(self, bank):
        # the Result keeps the flat layout: local queue b*G + g is row
        # b*G + g of lqbuf/lqhead/lqlen, and ggq/g_inq are (a, G)
        g, a = bank["lqhead"].shape
        return dict(
            bank, lqhead=bank["lqhead"].T.reshape(-1),
            lqlen=bank["lqlen"].T.reshape(-1),
            lqbuf=bank["lqbuf"].reshape(g, -1, a).transpose(2, 0, 1)
            .reshape(a * g, -1),
            ggq=bank["ggq"].T, g_inq=bank["g_inq"].T)

    def _update(self, p, n, win, acq_b, rel_b, bank):
        """The bank side of an access, dense over bank lanes.  The engine
        guarantees at most one winner per bank, an acquire or a release,
        and a lane reads and writes only its own column, so no two
        writes ever meet.  Returns the bank, the granted and the
        enqueued lanes, and each lane's side messages."""
        G, gsz, cap_l = self._geom(p, n)
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        turn_srv = bank["turn_srv"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]
        g_b = jnp.minimum(jnp.minimum(win, n - 1) // gsz, G - 1)

        # ---- acquire ----
        idle_b = cur_grp < 0                     # no turn in progress
        grant_b = acq_b & idle_b
        cur_grp = jnp.where(grant_b, g_b, cur_grp)
        turn_srv = jnp.where(grant_b, 0, turn_srv)
        # enqueue in the group-local queue and sleep (never full: cap_l
        # covers one outstanding RMW per member core — polling-free)
        enq_b = acq_b & ~idle_b
        len_b = take_lanes(lqlen, g_b)
        row_b = g_b * cap_l + (take_lanes(lqhead, g_b) + len_b) % cap_l
        lqbuf = put_lanes(lqbuf, row_b, enq_b, win)
        len_b = len_b + enq_b                    # the queue after the put
        lqlen = put_lanes(lqlen, g_b, enq_b, len_b)
        msgs = enq_b.astype(jnp.int32)           # intra-cluster SuccUpdate
        # first waiter of a non-serving group registers it globally
        reg_b = enq_b & (cur_grp != g_b) & ~take_lanes(g_inq, g_b)
        ggq = put_lanes(ggq, (gqhead + gqlen) % G, reg_b, g_b)
        gqlen = gqlen + reg_b
        g_inq = put_lanes(g_inq, g_b, reg_b, True)
        msgs = msgs + 2 * reg_b                  # global registration RT

        # ---- release (the releaser's group is always cur_grp) ----
        srv_b = turn_srv + 1                     # ops completed this turn
        # turn budget: with competitors registered, a group yields after
        # group_size ops even if its local queue still holds waiters —
        # round-robin fairness at cluster granularity
        exhausted_b = rel_b & (srv_b >= gsz) & (gqlen > 0)
        more_local_b = rel_b & (len_b > 0) & ~exhausted_b
        wake_grp = jnp.where(more_local_b, g_b, wake_grp)
        wake_tmr = jnp.where(more_local_b, self.local_delay, wake_tmr)
        msgs = msgs + more_local_b               # intra-cluster wake
        turn_srv = jnp.where(more_local_b, srv_b, turn_srv)
        # yielding with waiters left: re-register at the global tail
        re_reg_b = rel_b & (len_b > 0) & exhausted_b
        ggq = put_lanes(ggq, (gqhead + gqlen) % G, re_reg_b, g_b)
        gqlen = gqlen + re_reg_b
        g_inq = put_lanes(g_inq, g_b, re_reg_b, True)
        msgs = msgs + 2 * re_reg_b               # re-registration RT
        # turn over: local queue drained, or budget spent with competitors
        end_turn_b = rel_b & ((len_b == 0) | exhausted_b)
        have_next_b = end_turn_b & (gqlen > 0)
        next_g_b = take_lanes(ggq, gqhead)
        cur_grp = jnp.where(have_next_b, next_g_b, cur_grp)
        g_inq = put_lanes(g_inq, next_g_b, have_next_b, False)
        gqhead = jnp.where(have_next_b, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next_b
        wake_grp = jnp.where(have_next_b, next_g_b, wake_grp)
        wake_tmr = jnp.where(have_next_b, p.lat + 2, wake_tmr)
        turn_srv = jnp.where(have_next_b, 0, turn_srv)
        msgs = msgs + 2 * have_next_b            # cross-cluster wake RT
        # nothing left anywhere: the address goes idle
        cur_grp = jnp.where(end_turn_b & ~have_next_b, -1, cur_grp)

        bank = dict(bank, lqbuf=lqbuf, lqhead=lqhead, lqlen=lqlen, ggq=ggq,
                    gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, turn_srv=turn_srv,
                    wake_tmr=wake_tmr, wake_grp=wake_grp)
        return bank, grant_b, enq_b, msgs

    def on_access(self, ctx, cs, bank):
        p, wa = ctx.p, ctx.wa
        bank, grant_b, _, msgs = self._update(
            p, ctx.n, ctx.win_core, ctx.acq_b, ctx.rel_b, bank)
        granted = grant_b[wa]
        grant = ctx.is_acq & granted
        enq = ctx.is_acq & ~granted
        cs["st"] = jnp.where(grant, RESP, cs["st"])
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        cs["st"] = jnp.where(enq, SLEEP, cs["st"])
        cs["st"] = jnp.where(ctx.is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(ctx.is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(ctx.is_rel, NXT_WORK_DONE, cs["nxt"])
        cs["msgs"] = cs["msgs"] + msgs.sum()
        return cs, bank

    def fused_access(self, fx, bank):
        # block-local: the update reads no bank id, so a tile's lanes are
        # its own banks; the per-core grant/enqueue/release effects
        # become OUT_* codes
        bank, grant_b, enq_b, msgs = self._update(
            fx.p, fx.n, fx.win, fx.acq_b, fx.rel_b, bank)
        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(fx.rel_b, OUT_DONE, OUT_NONE))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        return bank, FusedOut(kind=kind, tmr=tmr, msgs=msgs.astype(jnp.int32))

    # ---- fault recovery (repro.faults) ----------------------------------
    # Unlike the flat FIFO protocols the current holder is NOT queued
    # (grantees skip the local queues; woken heads are popped), so
    # eviction cannot pop the dead core — instead it REPLAYS the release
    # handoff the dead owner would have performed: wake the serving
    # group's next local waiter, else hand the address to the next
    # registered group, else go idle.  The engine-tracked last grantee
    # (``owner``) tells the watchdog whether the holder is dead.
    def held(self, bank):
        return bank["cur_grp"] >= 0

    def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
        p, n = ctx.p, ctx.n
        G, _, _ = self._geom(p, n)
        lqlen = bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        turn_srv = bank["turn_srv"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]
        own_dead = (owner < n) & killed[jnp.clip(owner, 0, n - 1)]
        evict_b = stuck_b & own_dead
        g = jnp.clip(cur_grp, 0, G - 1)
        more_local = evict_b & (take_lanes(lqlen, g) > 0)
        wake_grp = jnp.where(more_local, g, wake_grp)
        wake_tmr = jnp.where(more_local, self.local_delay, wake_tmr)
        end_b = evict_b & ~more_local
        have_next = end_b & (gqlen > 0)
        next_g = take_lanes(ggq, gqhead)
        cur_grp = jnp.where(have_next, next_g, cur_grp)
        g_inq = put_lanes(g_inq, next_g, have_next, False)
        gqhead = jnp.where(have_next, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next
        wake_grp = jnp.where(have_next, next_g, wake_grp)
        wake_tmr = jnp.where(have_next, p.lat + 2, wake_tmr)
        turn_srv = jnp.where(evict_b, 0, turn_srv)
        cur_grp = jnp.where(end_b & ~have_next, -1, cur_grp)
        # live owner, no progress: the recorded wake was lost — re-send
        redeliver_b = (stuck_b & ~own_dead
                       & (take_lanes(lqlen, wake_grp) > 0))
        wake_tmr = jnp.where(redeliver_b, self.local_delay, wake_tmr)
        cs["msgs"] = cs["msgs"] + 2 * (more_local | have_next
                                       | redeliver_b).sum()
        bank.update(ggq=ggq, gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, turn_srv=turn_srv,
                    wake_tmr=wake_tmr, wake_grp=wake_grp)
        kind = jnp.where(evict_b, OUT_EVICT,
                         jnp.where(redeliver_b, OUT_REDELIVER,
                                   OUT_NONE)).astype(jnp.int32)
        return cs, bank, kind

    def on_wake(self, ctx, cs, bank):
        G, _, cap_l = self._geom(ctx.p, ctx.n)
        wake_tmr, wg = bank["wake_tmr"], bank["wake_grp"]
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        fire = wake_tmr == 1
        wake_tmr = jnp.maximum(wake_tmr - 1, 0)
        # the woken group's head and length are selects over the G rows
        # of each bank's column; its head core sits in lqbuf's row
        # wg * cap_l + head of that column
        hd, ln = take_lanes(lqhead, wg), take_lanes(lqlen, wg)
        valid = fire & (ln > 0)
        row = wg * cap_l + hd
        if ctx.to_cores == "gather":
            # each core reads the head of its own bank's woken queue
            r, live = at_cores(ctx.wa, row, valid)
            fire_core = jnp.where(live, lqbuf[r, ctx.wa], ctx.n)
        else:
            ba = ctx.ba if ctx.ba is not None else jnp.arange(ctx.a)
            fire_core = jnp.where(valid, lqbuf[row, ba], ctx.n)
        woken = fired(ctx.to_cores, fire_core, ctx.wc)
        cs["st"] = jnp.where(woken, MOD, cs["st"])
        cs["tmr"] = jnp.where(woken, ctx.mod_dur, cs["tmr"])
        # pop the woken head: it is now the address's active holder
        lqhead = put_lanes(lqhead, wg, valid, (hd + 1) % cap_l)
        lqlen = put_lanes(lqlen, wg, valid, ln - 1)
        bank.update(wake_tmr=wake_tmr, lqhead=lqhead, lqlen=lqlen)
        return cs, bank, (wake_tmr == 1).sum()
