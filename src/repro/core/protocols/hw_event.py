"""``hw_event`` — per-cluster hardware barrier/event unit (Glaser et
al., arXiv:2004.06662) over a hierarchical topology.

Each cluster owns a dedicated synchronization unit next to its cores: a
waiter registers with its **local** event unit and clock-gates (the
unit holds the wait line; registering costs no NoC traffic beyond the
request itself, and the parked core burns sleep-rate energy).  When the
resource frees inside the cluster, the unit raises the wakeup line — a
**single-cycle intra-cluster broadcast**, an order of magnitude below
the ``lat``-cycle NoC round trip of a message-based wake.  Across
clusters the units form a combining tree: a cluster with waiters
asserts one upward combine signal (1 message — a line toggle, not a
round trip), and a releasing cluster whose local waiters drained hands
the resource to the next registered cluster over the NoC
(``lat + 1`` — the cross-cluster wire plus the receiving unit's
broadcast cycle).

Structurally this is ``colibri_hier`` with the reservation Qnodes
replaced by hardware event units — the cluster-local queues and the
global FIFO of clusters survive, but there is **no turn budget**: a
hardware unit serves its cluster until the local wait set drains
(service order inside a cluster is FIFO; cross-cluster fairness is
FIFO over *drain epochs*, the documented behaviour of a wired event
unit, which has no counter to meter turns with).  Retry-free and
polling-free by construction; grantees bypass the local queues, so
``queue_depth`` counts sleepers only (``queue_counts_holder=False``).

The natural host is a hierarchical topology (``Spec(topology=
"cluster2", ...)``): the unit's cluster is then exactly the cluster
the NoC routes the core through (same block placement), so local wakes
really are the messages the topology's link model keeps off the
cross-cluster links.  On the flat topology the plugin still runs (the
event tree degenerates to ``n_groups`` units on one crossbar).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.protocols.base import (MOD, NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_EVICT, OUT_GRANT, OUT_NONE,
                                       OUT_REDELIVER, OUT_SLEEP, RESP, SLEEP,
                                       Contract, FusedOut, Protocol,
                                       onehot_rows, put_cols, scatter_rows,
                                       take_cols, take_rows)
from repro.core.protocols.registry import register


@register
class HwEvent(Protocol):
    name = "hw_event"
    uses_queue = True
    local_delay = 1          # single-cycle intra-cluster wakeup broadcast
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=False,
                        max_hot_scatters=10)  # measured 8 (+2 headroom)

    @staticmethod
    def _geom(p, n):
        """(units, cluster_size, local queue capacity) — all static.
        One event unit per topology cluster when the machine is
        hierarchical; one per ``n_groups`` on the flat crossbar."""
        knob = (p.clusters if getattr(p, "topology", "flat") != "flat"
                else p.n_groups)
        g = max(1, min(knob, n))
        gsz = max(1, n // g)
        cap_l = max(gsz, n - (g - 1) * gsz)  # last cluster may be larger
        return g, gsz, cap_l

    def init_bank_state(self, p, a, n, q_cap):
        g, _, cap_l = self._geom(p, n)
        return dict(
            lqbuf=jnp.full((a * g, cap_l), -1, jnp.int32),
            lqhead=jnp.zeros((a * g,), jnp.int32),
            lqlen=jnp.zeros((a * g,), jnp.int32),
            ggq=jnp.full((a, g), -1, jnp.int32),    # FIFO of cluster ids
            gqhead=jnp.zeros((a,), jnp.int32),
            gqlen=jnp.zeros((a,), jnp.int32),
            g_inq=jnp.zeros((a, g), bool),
            cur_grp=jnp.full((a,), -1, jnp.int32),  # cluster holding it
            wake_tmr=jnp.zeros((a,), jnp.int32),
            # CLUSTER whose local wait set to wake (group id, not the
            # flat (addr, cluster) queue id — kernel-tiling safe, same
            # as colibri_hier's wake_grp)
            wake_grp=jnp.zeros((a,), jnp.int32),
        )

    def queue_depth(self, bank):
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
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]

        # winning core's cluster and flat (addr, cluster) wait-set id;
        # all bank-state writes are dense (≤1 winner per bank)
        g_b = jnp.minimum(jnp.minimum(win, ctx.n - 1) // gsz, G - 1)
        lq_b = ba * G + g_b
        oob_a, oob_lq = ctx.a, ctx.a * G

        # ---- acquire ----
        idle_b = cur_grp < 0
        idle = idle_b[wa]
        grant = is_acq & idle
        grant_b = acq_b & idle_b
        cur_grp = jnp.where(grant_b, g_b, cur_grp)
        cs["st"] = jnp.where(grant, RESP, cs["st"])
        cs["tmr"] = jnp.where(grant, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(grant, NXT_MOD, cs["nxt"])
        # register with the local event unit and clock-gate.  The unit
        # is wired to its cores: registration is absorbed into the
        # request the core already sent (no extra NoC messages — the
        # Qnode SuccessorUpdate this replaces cost 1).
        enq = is_acq & ~idle
        enq_b = acq_b & ~idle_b
        slot_b = (lqhead[lq_b] + lqlen[lq_b]) % cap_l
        put_lq = jnp.where(enq_b, lq_b, oob_lq)
        lqbuf = lqbuf.at[put_lq, slot_b].set(win, mode="drop")
        lqlen = lqlen.at[put_lq].add(1, mode="drop")
        cs["st"] = jnp.where(enq, SLEEP, cs["st"])
        # first waiter of a non-holding cluster asserts the upward
        # combine line: ONE message up the tree, no round trip
        reg_b = enq_b & (cur_grp != g_b) & ~g_inq[ba, g_b]
        gslot_b = (gqhead + gqlen) % G
        reg_a = jnp.where(reg_b, ba, oob_a)
        ggq = ggq.at[reg_a, gslot_b].set(g_b, mode="drop")
        gqlen = gqlen + reg_b
        g_inq = g_inq.at[reg_a, g_b].set(True, mode="drop")
        cs["msgs"] = cs["msgs"] + reg_b.sum()

        # ---- release (releaser's cluster always == cur_grp[wa]) ----
        # the unit serves its cluster until the local wait set drains:
        # single-cycle broadcast wake, zero NoC messages
        more_local_b = rel_b & (lqlen[lq_b] > 0)
        wake_grp = jnp.where(more_local_b, g_b, wake_grp)
        wake_tmr = jnp.where(more_local_b, self.local_delay, wake_tmr)
        # drained: hand the resource to the next registered cluster
        # (cross-cluster wire + the receiving unit's broadcast cycle)
        end_turn_b = rel_b & (lqlen[lq_b] == 0)
        have_next_b = end_turn_b & (gqlen > 0)
        next_g_b = ggq[ba, gqhead]
        cur_grp = jnp.where(have_next_b, next_g_b, cur_grp)
        g_inq = g_inq.at[jnp.where(have_next_b, ba, oob_a), next_g_b].set(
            False, mode="drop")
        gqhead = jnp.where(have_next_b, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next_b
        wake_grp = jnp.where(have_next_b, next_g_b, wake_grp)
        wake_tmr = jnp.where(have_next_b, p.lat + 1, wake_tmr)
        cs["msgs"] = cs["msgs"] + 2 * have_next_b.sum()  # x-cluster handoff
        # nothing left anywhere: the resource goes idle
        cur_grp = jnp.where(end_turn_b & ~have_next_b, -1, cur_grp)
        cs["st"] = jnp.where(is_rel, RESP, cs["st"])
        cs["tmr"] = jnp.where(is_rel, p.lat, cs["tmr"])
        cs["nxt"] = jnp.where(is_rel, NXT_WORK_DONE, cs["nxt"])

        bank.update(lqbuf=lqbuf, lqhead=lqhead, lqlen=lqlen, ggq=ggq,
                    gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, wake_tmr=wake_tmr, wake_grp=wake_grp)
        return cs, bank

    def fused_access(self, fx, bank):
        # on_access restated block-locally for the Pallas engine-step
        # kernel: local iota bank ids, one-hot selects for the indexed
        # reads and writes, per-core effects as OUT_* codes
        G, gsz, cap_l = self._geom(fx.p, fx.n)
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
        wake_tmr, wake_grp = bank["wake_tmr"], bank["wake_grp"]
        a = cur_grp.shape[0]                     # banks in this block
        ba = jnp.arange(a, dtype=jnp.int32)
        g_b = jnp.minimum(jnp.minimum(fx.win, fx.n - 1) // gsz, G - 1)
        lq_b = ba * G + g_b

        # ---- acquire ----
        idle_b = cur_grp < 0
        grant_b = fx.acq_b & idle_b
        cur_grp = jnp.where(grant_b, g_b, cur_grp)
        enq_b = fx.acq_b & ~idle_b
        len_b = take_rows(lqlen, lq_b)
        slot_b = (take_rows(lqhead, lq_b) + len_b) % cap_l
        put_lq = onehot_rows(lq_b, enq_b, a * G)
        put_r = scatter_rows(put_lq, jnp.ones_like(lq_b))   # 0/1 per row
        lqbuf = put_cols(lqbuf, scatter_rows(put_lq, slot_b), put_r > 0,
                         scatter_rows(put_lq, fx.win))
        lqlen = lqlen + put_r
        len_b = len_b + enq_b                    # own rows only (see hier)
        reg_b = enq_b & (cur_grp != g_b) & ~take_cols(g_inq, g_b)
        gslot_b = (gqhead + gqlen) % G
        ggq = put_cols(ggq, gslot_b, reg_b, g_b)
        gqlen = gqlen + reg_b
        g_inq = put_cols(g_inq, g_b, reg_b, True)
        msgs = reg_b.astype(jnp.int32)           # upward combine line

        # ---- release ----
        more_local_b = fx.rel_b & (len_b > 0)
        wake_grp = jnp.where(more_local_b, g_b, wake_grp)
        wake_tmr = jnp.where(more_local_b, self.local_delay, wake_tmr)
        end_turn_b = fx.rel_b & (len_b == 0)
        have_next_b = end_turn_b & (gqlen > 0)
        next_g_b = take_cols(ggq, gqhead)
        cur_grp = jnp.where(have_next_b, next_g_b, cur_grp)
        g_inq = put_cols(g_inq, next_g_b, have_next_b, False)
        gqhead = jnp.where(have_next_b, (gqhead + 1) % G, gqhead)
        gqlen = gqlen - have_next_b
        wake_grp = jnp.where(have_next_b, next_g_b, wake_grp)
        wake_tmr = jnp.where(have_next_b, fx.p.lat + 1, wake_tmr)
        msgs = msgs + 2 * have_next_b            # cross-cluster handoff
        cur_grp = jnp.where(end_turn_b & ~have_next_b, -1, cur_grp)

        kind = jnp.where(
            grant_b, OUT_GRANT,
            jnp.where(enq_b, OUT_SLEEP,
                      jnp.where(fx.rel_b, OUT_DONE, OUT_NONE))
        ).astype(jnp.int32)
        tmr = jnp.full_like(kind, fx.p.lat)
        bank = dict(bank, lqbuf=lqbuf, lqhead=lqhead, lqlen=lqlen, ggq=ggq,
                    gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, wake_tmr=wake_tmr, wake_grp=wake_grp)
        return bank, FusedOut(kind=kind, tmr=tmr, msgs=msgs.astype(jnp.int32))

    # ---- fault recovery (repro.faults) ----------------------------------
    # The holder is not queued (grantees bypass the wait sets), so a dead
    # owner's eviction REPLAYS the release handoff it would have issued:
    # wake the holding cluster's next local waiter, else hand the
    # resource to the next registered cluster, else go idle.
    def held(self, bank):
        return bank["cur_grp"] >= 0

    def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
        p, n, ba = ctx.p, ctx.n, ctx.ba
        G, _, _ = self._geom(p, n)
        lqlen = bank["lqlen"]
        ggq, gqhead, gqlen = bank["ggq"], bank["gqhead"], bank["gqlen"]
        g_inq, cur_grp = bank["g_inq"], bank["cur_grp"]
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
        wake_tmr = jnp.where(have_next, p.lat + 1, wake_tmr)
        cur_grp = jnp.where(end_b & ~have_next, -1, cur_grp)
        # live owner, no progress: the recorded wake was lost — re-raise
        redeliver_b = (stuck_b & ~own_dead
                       & (lqlen[ba * G + wake_grp] > 0))
        wake_tmr = jnp.where(redeliver_b, self.local_delay, wake_tmr)
        cs["msgs"] = cs["msgs"] + 2 * (more_local | have_next
                                       | redeliver_b).sum()
        bank.update(ggq=ggq, gqhead=gqhead, gqlen=gqlen, g_inq=g_inq,
                    cur_grp=cur_grp, wake_tmr=wake_tmr, wake_grp=wake_grp)
        kind = jnp.where(evict_b, OUT_EVICT,
                         jnp.where(redeliver_b, OUT_REDELIVER,
                                   OUT_NONE)).astype(jnp.int32)
        return cs, bank, kind

    def on_wake(self, ctx, cs, bank):
        G, _, cap_l = self._geom(ctx.p, ctx.n)
        wake_tmr = bank["wake_tmr"]
        ba = ctx.ba if ctx.ba is not None else jnp.arange(ctx.a)
        wq = ba * G + bank["wake_grp"]      # flat local wait-set id
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        fire = wake_tmr == 1
        wake_tmr = jnp.maximum(wake_tmr - 1, 0)
        head_core = lqbuf[wq, lqhead[wq]]
        valid = fire & (lqlen[wq] > 0)
        fire_core = jnp.where(valid, head_core, ctx.n)
        woken = jnp.zeros((ctx.n,), bool).at[fire_core].set(True, mode="drop")
        cs["st"] = jnp.where(woken, MOD, cs["st"])
        cs["tmr"] = jnp.where(woken, ctx.mod_dur, cs["tmr"])
        # pop the woken head: it is now the resource's active holder
        oob = jnp.where(valid, wq, ctx.a * G)
        lqhead = (lqhead.at[oob].add(1, mode="drop")) % cap_l
        lqlen = lqlen.at[oob].add(-1, mode="drop")
        bank.update(wake_tmr=wake_tmr, lqhead=lqhead, lqlen=lqlen)
        return cs, bank, (wake_tmr == 1).sum()
