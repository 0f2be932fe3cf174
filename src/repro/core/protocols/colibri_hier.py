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
                                       at_cores, fired, onehot_rows,
                                       put_cols, scatter_rows, take_cols,
                                       take_rows)
from repro.core.protocols.registry import register


@register
class ColibriHier(Protocol):
    name = "colibri_hier"
    uses_queue = True
    local_delay = 2          # intra-cluster Qnode bounce
    # retry-free wait-class like flat colibri, but grantees bypass the
    # local queues (woken heads are popped), so queue_depth counts the
    # sleepers ONLY — the conservation rule the PR 6 wake_grp aliasing
    # bug violated
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=False,
                        max_hot_scatters=12)

    @staticmethod
    def _geom(p, n):
        """(n_groups, group_size, local queue capacity) — all static."""
        g = max(1, min(p.n_groups, n))
        gsz = max(1, n // g)
        cap_l = max(gsz, n - (g - 1) * gsz)  # last group may be larger
        return g, gsz, cap_l

    def init_bank_state(self, p, a, n, q_cap):
        g, _, cap_l = self._geom(p, n)
        return dict(
            lqbuf=jnp.full((a * g, cap_l), -1, jnp.int32),
            lqhead=jnp.zeros((a * g,), jnp.int32),
            lqlen=jnp.zeros((a * g,), jnp.int32),
            ggq=jnp.full((a, g), -1, jnp.int32),    # FIFO of group ids
            gqhead=jnp.zeros((a,), jnp.int32),
            gqlen=jnp.zeros((a,), jnp.int32),
            g_inq=jnp.zeros((a, g), bool),
            cur_grp=jnp.full((a,), -1, jnp.int32),  # group holding the turn
            turn_srv=jnp.zeros((a,), jnp.int32),    # ops served this turn
            wake_tmr=jnp.zeros((a,), jnp.int32),
            # GROUP whose local queue to wake: storing the group id (not
            # the flat (addr, group) queue id) keeps the value meaningful
            # under the Pallas kernel's bank tiling — a block-local flat
            # id would alias another bank's queue once the block offset
            # is stripped; on_wake rebuilds the flat id from global ids
            wake_grp=jnp.zeros((a,), jnp.int32),
        )

    def queue_depth(self, bank):
        # total waiters per bank = its G group-local queues summed
        # (flat queue id is bank*G + group, so a (a, G) reshape lines up)
        a = bank["cur_grp"].shape[0]
        return bank["lqlen"].reshape(a, -1).sum(axis=1)

    def on_access(self, ctx, cs, bank):
        p, wa = ctx.p, ctx.wa
        is_acq, is_rel = ctx.is_acq, ctx.is_rel
        acq_b, rel_b, win, ba = ctx.acq_b, ctx.rel_b, ctx.win_core, ctx.ba
        G, gsz, cap_l = self._geom(p, ctx.n)
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        turn_srv = bank["turn_srv"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]

        # bank-side: the winning core's group and flat queue id.
        # All bank/queue state writes below are dense over banks (or
        # a-lane scatters into the (a*G,) local-queue arrays): the
        # engine guarantees ≤1 winner per bank, each either an acquire
        # or a release, so no two writes ever hit the same bank's state
        # and the former n-lane masked scatters collapse to vector ops.
        g_b = jnp.minimum(jnp.minimum(win, ctx.n - 1) // gsz, G - 1)
        lq_b = ba * G + g_b                      # flat (addr, group) id
        oob_a, oob_lq = ctx.a, ctx.a * G

        # ---- acquire ----
        idle_b = cur_grp < 0                     # no turn in progress
        idle = idle_b[wa]
        grant = is_acq & idle
        grant_b = acq_b & idle_b
        cur_grp = jnp.where(grant_b, g_b, cur_grp)
        turn_srv = jnp.where(grant_b, 0, turn_srv)
        cs["st"] = jnp.where(grant, RESP, cs["st"])
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        # enqueue in the group-local queue and sleep (never full: cap_l
        # covers one outstanding RMW per member core — polling-free)
        enq = is_acq & ~idle
        enq_b = acq_b & ~idle_b
        slot_b = (lqhead[lq_b] + lqlen[lq_b]) % cap_l
        put_lq = jnp.where(enq_b, lq_b, oob_lq)
        lqbuf = lqbuf.at[put_lq, slot_b].set(win, mode="drop")
        lqlen = lqlen.at[put_lq].add(1, mode="drop")
        cs["st"] = jnp.where(enq, SLEEP, cs["st"])
        cs["msgs"] = cs["msgs"] + enq_b.sum()    # intra-cluster SuccUpdate
        # first waiter of a non-serving group registers it globally
        reg_b = enq_b & (cur_grp != g_b) & ~g_inq[ba, g_b]
        gslot_b = (gqhead + gqlen) % G
        reg_a = jnp.where(reg_b, ba, oob_a)
        ggq = ggq.at[reg_a, gslot_b].set(g_b, mode="drop")
        gqlen = gqlen + reg_b
        g_inq = g_inq.at[reg_a, g_b].set(True, mode="drop")
        cs["msgs"] = cs["msgs"] + 2 * reg_b.sum()  # global registration RT

        # ---- release (releaser's group always == cur_grp[wa]) ----
        srv_b = turn_srv + 1                     # ops completed this turn
        # turn budget: with competitors registered, a group yields after
        # group_size ops even if its local queue still holds waiters —
        # round-robin fairness at cluster granularity
        exhausted_b = rel_b & (srv_b >= gsz) & (gqlen > 0)
        more_local_b = rel_b & (lqlen[lq_b] > 0) & ~exhausted_b
        wake_grp = jnp.where(more_local_b, g_b, wake_grp)
        wake_tmr = jnp.where(more_local_b, self.local_delay, wake_tmr)
        cs["msgs"] = cs["msgs"] + more_local_b.sum()  # intra-cluster wake
        turn_srv = jnp.where(more_local_b, srv_b, turn_srv)
        # yielding with waiters left: re-register at the global tail
        re_reg_b = rel_b & (lqlen[lq_b] > 0) & exhausted_b
        tail_b = (gqhead + gqlen) % G
        re_reg_a = jnp.where(re_reg_b, ba, oob_a)
        ggq = ggq.at[re_reg_a, tail_b].set(g_b, mode="drop")
        gqlen = gqlen + re_reg_b
        g_inq = g_inq.at[re_reg_a, g_b].set(True, mode="drop")
        cs["msgs"] = cs["msgs"] + 2 * re_reg_b.sum()  # re-registration RT
        # turn over: local queue drained, or budget spent with competitors
        end_turn_b = rel_b & ((lqlen[lq_b] == 0) | exhausted_b)
        have_next_b = end_turn_b & (gqlen > 0)
        next_g_b = ggq[ba, gqhead]
        cur_grp = jnp.where(have_next_b, next_g_b, cur_grp)
        g_inq = g_inq.at[jnp.where(have_next_b, ba, oob_a), next_g_b].set(
            False, mode="drop")
        gqhead = jnp.where(have_next_b, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next_b
        wake_grp = jnp.where(have_next_b, next_g_b, wake_grp)
        wake_tmr = jnp.where(have_next_b, p.lat + 2, wake_tmr)
        turn_srv = jnp.where(have_next_b, 0, turn_srv)
        cs["msgs"] = cs["msgs"] + 2 * have_next_b.sum()  # x-cluster wake RT
        # nothing left anywhere: the address goes idle
        cur_grp = jnp.where(end_turn_b & ~have_next_b, -1, cur_grp)
        cs["st"] = jnp.where(is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(is_rel, NXT_WORK_DONE, cs["nxt"])

        bank.update(lqbuf=lqbuf, lqhead=lqhead, lqlen=lqlen, ggq=ggq,
                    gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, turn_srv=turn_srv,
                    wake_tmr=wake_tmr, wake_grp=wake_grp)
        return cs, bank

    def fused_access(self, fx, bank):
        # the on_access dense bank updates, restated block-locally: bank
        # ids come from a local iota over this block's lanes (the flat
        # (addr, group) queue ids follow from it), indexed reads and
        # writes are one-hot selects (the kernel lowers no gather or
        # scatter), and the per-core grant/enqueue/release effects become
        # OUT_* codes.
        G, gsz, cap_l = self._geom(fx.p, fx.n)
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        turn_srv = bank["turn_srv"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]
        a = cur_grp.shape[0]                     # banks in this block
        ba = jnp.arange(a, dtype=jnp.int32)
        g_b = jnp.minimum(jnp.minimum(fx.win, fx.n - 1) // gsz, G - 1)
        lq_b = ba * G + g_b

        # ---- acquire ----
        idle_b = cur_grp < 0
        grant_b = fx.acq_b & idle_b
        cur_grp = jnp.where(grant_b, g_b, cur_grp)
        turn_srv = jnp.where(grant_b, 0, turn_srv)
        enq_b = fx.acq_b & ~idle_b
        len_b = take_rows(lqlen, lq_b)
        slot_b = (take_rows(lqhead, lq_b) + len_b) % cap_l
        put_lq = onehot_rows(lq_b, enq_b, a * G)
        put_r = scatter_rows(put_lq, jnp.ones_like(lq_b))   # 0/1 per row
        lqbuf = put_cols(lqbuf, scatter_rows(put_lq, slot_b), put_r > 0,
                         scatter_rows(put_lq, fx.win))
        lqlen = lqlen + put_r
        # a lane writes only its own bank's queue rows, so its queue
        # length after the enqueue is its own read plus its own put
        len_b = len_b + enq_b
        msgs = enq_b.astype(jnp.int32)           # intra-cluster SuccUpdate
        reg_b = enq_b & (cur_grp != g_b) & ~take_cols(g_inq, g_b)
        gslot_b = (gqhead + gqlen) % G
        ggq = put_cols(ggq, gslot_b, reg_b, g_b)
        gqlen = gqlen + reg_b
        g_inq = put_cols(g_inq, g_b, reg_b, True)
        msgs = msgs + 2 * reg_b                  # global registration RT

        # ---- release ----
        srv_b = turn_srv + 1
        exhausted_b = fx.rel_b & (srv_b >= gsz) & (gqlen > 0)
        more_local_b = fx.rel_b & (len_b > 0) & ~exhausted_b
        wake_grp = jnp.where(more_local_b, g_b, wake_grp)
        wake_tmr = jnp.where(more_local_b, self.local_delay, wake_tmr)
        msgs = msgs + more_local_b               # intra-cluster wake
        turn_srv = jnp.where(more_local_b, srv_b, turn_srv)
        re_reg_b = fx.rel_b & (len_b > 0) & exhausted_b
        tail_b = (gqhead + gqlen) % G
        ggq = put_cols(ggq, tail_b, re_reg_b, g_b)
        gqlen = gqlen + re_reg_b
        g_inq = put_cols(g_inq, g_b, re_reg_b, True)
        msgs = msgs + 2 * re_reg_b               # re-registration RT
        end_turn_b = fx.rel_b & ((len_b == 0) | exhausted_b)
        have_next_b = end_turn_b & (gqlen > 0)
        next_g_b = take_cols(ggq, gqhead)
        cur_grp = jnp.where(have_next_b, next_g_b, cur_grp)
        g_inq = put_cols(g_inq, next_g_b, have_next_b, False)
        gqhead = jnp.where(have_next_b, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next_b
        wake_grp = jnp.where(have_next_b, next_g_b, wake_grp)
        wake_tmr = jnp.where(have_next_b, fx.p.lat + 2, wake_tmr)
        turn_srv = jnp.where(have_next_b, 0, turn_srv)
        msgs = msgs + 2 * have_next_b            # cross-cluster wake RT
        cur_grp = jnp.where(end_turn_b & ~have_next_b, -1, cur_grp)

        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(fx.rel_b, OUT_DONE, OUT_NONE))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        bank = dict(bank, lqbuf=lqbuf, lqhead=lqhead, lqlen=lqlen, ggq=ggq,
                    gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, turn_srv=turn_srv,
                    wake_tmr=wake_tmr, wake_grp=wake_grp)
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
        p, n, ba = ctx.p, ctx.n, ctx.ba
        G, _, _ = self._geom(p, n)
        lqlen = bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        turn_srv = bank["turn_srv"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]
        own_dead = (owner < n) & killed[jnp.clip(owner, 0, n - 1)]
        evict_b = stuck_b & own_dead
        g = jnp.clip(cur_grp, 0, G - 1)
        more_local = evict_b & (lqlen[ba * G + g] > 0)
        wake_grp = jnp.where(more_local, g, wake_grp)
        wake_tmr = jnp.where(more_local, self.local_delay, wake_tmr)
        end_b = evict_b & ~more_local
        have_next = end_b & (gqlen > 0)
        next_g = ggq[ba, gqhead]
        cur_grp = jnp.where(have_next, next_g, cur_grp)
        g_inq = g_inq.at[jnp.where(have_next, ba, ctx.a), next_g].set(
            False, mode="drop")
        gqhead = jnp.where(have_next, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next
        wake_grp = jnp.where(have_next, next_g, wake_grp)
        wake_tmr = jnp.where(have_next, p.lat + 2, wake_tmr)
        turn_srv = jnp.where(evict_b, 0, turn_srv)
        cur_grp = jnp.where(end_b & ~have_next, -1, cur_grp)
        # live owner, no progress: the recorded wake was lost — re-send
        redeliver_b = (stuck_b & ~own_dead
                       & (lqlen[ba * G + wake_grp] > 0))
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
        wake_tmr = bank["wake_tmr"]
        ba = ctx.ba if ctx.ba is not None else jnp.arange(ctx.a)
        wq = ba * G + bank["wake_grp"]      # flat local-queue id
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        fire = wake_tmr == 1
        wake_tmr = jnp.maximum(wake_tmr - 1, 0)
        gather = ctx.to_cores == "gather"
        if gather:
            # queue (b, g) is row b, column g of the (a, G) view: the
            # woken group's head and length are selects, and each core
            # reads the head of its own bank's woken queue
            wg = bank["wake_grp"]
            lqhead, lqlen = lqhead.reshape(ctx.a, G), lqlen.reshape(ctx.a, G)
            hd, ln = take_cols(lqhead, wg), take_cols(lqlen, wg)
            valid = fire & (ln > 0)
            q, h, live = at_cores(ctx.wa, wq, hd, valid)
            fire_core = jnp.where(live, lqbuf[q, h], ctx.n)
        else:
            head_core = lqbuf[wq, lqhead[wq]]
            valid = fire & (lqlen[wq] > 0)
            fire_core = jnp.where(valid, head_core, ctx.n)
        woken = fired(ctx.to_cores, fire_core, ctx.wc)
        cs["st"] = jnp.where(woken, MOD, cs["st"])
        cs["tmr"] = jnp.where(woken, ctx.mod_dur, cs["tmr"])
        # pop the woken head: it is now the address's active holder
        if gather:
            lqhead = put_cols(lqhead, wg, valid, (hd + 1) % cap_l).reshape(-1)
            lqlen = put_cols(lqlen, wg, valid, ln - 1).reshape(-1)
        else:
            oob = jnp.where(valid, wq, ctx.a * G)
            lqhead = (lqhead.at[oob].add(1, mode="drop")) % cap_l
            lqlen = lqlen.at[oob].add(-1, mode="drop")
        bank.update(wake_tmr=wake_tmr, lqhead=lqhead, lqlen=lqlen)
        return cs, bank, (wake_tmr == 1).sum()
