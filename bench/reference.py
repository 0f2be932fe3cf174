"""Plain reference of the simulated machine, written apart from the program.

The benchmark decides ``correct`` by comparing every statistic the
simulator returns with what this file computes for the same point.  It
imports nothing of ``repro``: the machine model (cores running a
micro-op program, a bandwidth-capped network with head-of-line
blocking, single-ported banks serving their oldest parked request, the
protocols' bank-side rules, the cluster topology's link levels) is
written out again here from its description, as one ``lax.scan`` step
per simulated cycle over plain per-core and per-bank arrays.

What the model fixes down to the bit is part of its definition and is
restated here: the counter hash that draws addresses and backoff
jitter, the Zipf inverse CDF in float32, the rotating priority
``(core + 97 * cycle) mod n``, the staggered first timers and the
geometric latency bins.

Supported: the protocols ``colibri``, ``lrscwait``, ``mwait_lock``,
``lrsc``, ``amo_lock``, ``colibri_hier``; the workloads ``rmw_loop``,
``ms_queue``, ``treiber_stack``, ``zipf_histogram``, ``barrier_phases``;
the ``flat`` and ``cluster2`` topologies.  No workers, fault plans,
telemetry or traces (``cells.reference_point`` refuses such a point).

``broken`` names the control, the reference with one stated guarantee
broken: ``"fifo"`` — each bank serves its NEWEST parked request instead
of its oldest (first-come service at a bank); ``"latency"`` — a
completion's latency runs from its request's arrival at the bank, one
network latency after it is sent, instead of from the core's first
request.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BIG = np.iinfo(np.int32).max
# core states, request phases, response follow-ups
WORK, REQ, SLEEP, MOD, BACKOFF, RESP, BARWAIT = range(7)
ACQ, REL = 0, 1
AFTER_DONE, AFTER_MOD, AFTER_BACKOFF = 0, 1, 2
# per-bank outcome of the bank-side rule
NONE, GRANT, DONE, FAIL, WAIT = range(5)
LAT_BINS, LAT_SUB = 64, 4

# workload programs: per step (barrier?, pre = pm*work + pa, address
# mode, fixed address, modify = mm*modify + ma)
UNIFORM, FIXED, ZIPF = 0, 1, 2
PROGRAMS = {
    "rmw_loop": [(False, 1, 0, UNIFORM, 0, 1, 0)],
    "zipf_histogram": [(False, 1, 0, ZIPF, 0, 1, 0)],
    # enqueue swings the tail word (1), dequeue the head word (0)
    "ms_queue": [(False, 1, 0, FIXED, 1, 1, 0),
                 (False, 0, 2, FIXED, 0, 1, 0)],
    "treiber_stack": [(False, 1, 0, FIXED, 0, 1, 0),
                      (False, 0, 2, FIXED, 0, 1, 0)],
    # compute 4*work, then an arrival atomic on word 0 and a barrier
    "barrier_phases": [(True, 4, 0, FIXED, 0, 0, 1)],
}
QUEUE_PROTOCOLS = ("colibri", "lrscwait", "mwait_lock", "colibri_hier")
PROTOCOLS = QUEUE_PROTOCOLS + ("lrsc", "amo_lock")
#: link levels of the hierarchical topologies: (extra cycles, bw divisor)
LEVELS = {"flat": (), "cluster2": ((8, 4),)}
#: the traced scalars of a point
DYN = ("seed", "n_addrs", "lat", "work", "modify", "backoff",
       "backoff_exp", "net_bw", "hol_block", "zipf_skew")


def mix(x):
    """The model's counter hash: Knuth multiplicative, top 24 bits."""
    return (x.astype(jnp.uint32) * jnp.uint32(2654435761)) >> 8


def zipf(h24, n_addrs, skew_pct):
    """Bounded power-law address from a 24-bit hash (float32 inverse CDF;
    log-uniform within 1e-3 of s = 1)."""
    u = h24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    top = jnp.asarray(n_addrs).astype(jnp.float32) + 1.0
    s = jnp.asarray(skew_pct).astype(jnp.float32) * jnp.float32(0.01)
    om = 1.0 - s
    near1 = jnp.abs(om) < 1e-3
    safe_om = jnp.where(near1, 1.0, om)
    x_gen = (1.0 + u * (top ** safe_om - 1.0)) ** (1.0 / safe_om)
    x = jnp.where(near1, top ** u, x_gen)
    hi = jnp.asarray(n_addrs).astype(jnp.int32) - 1
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, hi)


def bank_count(n_addrs: int) -> int:
    """Banks the simulator allocates for ``n_addrs`` live addresses: the
    next power of two (the live count hashes into the first n_addrs)."""
    return 1 << max(n_addrs - 1, 0).bit_length()


def _groups(n_groups, n):
    g = max(1, min(n_groups, n))
    gsz = max(1, n // g)
    return g, gsz, max(gsz, n - (g - 1) * gsz)


def _topology(topology, clusters, n, a):
    """(hops, extra, [crossing mask per level]) as (n, a) numpy tables."""
    levels = LEVELS[topology]
    c = max(1, min(clusters, n))
    core_cl = np.minimum(np.arange(n) // max(1, n // c), c - 1)
    bank_cl = np.arange(a) % max(1, min(clusters, a))
    hops = np.ones((n, a), np.int32)
    extra = np.zeros((n, a), np.int32)
    cross = []
    for lv, (xlat, _) in enumerate(levels):
        x = (core_cl >> lv)[:, None] != (bank_cl >> lv)[None, :]
        cross.append(x)
        hops = hops + 2 * x
        extra = extra + xlat * x
    return hops, extra, cross


def _init_bank(protocol, a, n, q):
    if protocol in ("colibri", "lrscwait", "mwait_lock"):
        return dict(qbuf=jnp.full((a, q), -1, jnp.int32),
                    qhead=jnp.zeros((a,), jnp.int32),
                    qlen=jnp.zeros((a,), jnp.int32),
                    wake_tmr=jnp.zeros((a,), jnp.int32))
    if protocol == "colibri_hier":
        g, _, cap = q
        return dict(lqbuf=jnp.full((a * g, cap), -1, jnp.int32),
                    lqhead=jnp.zeros((a * g,), jnp.int32),
                    lqlen=jnp.zeros((a * g,), jnp.int32),
                    ggq=jnp.full((a, g), -1, jnp.int32),
                    gqhead=jnp.zeros((a,), jnp.int32),
                    gqlen=jnp.zeros((a,), jnp.int32),
                    g_inq=jnp.zeros((a, g), bool),
                    cur_grp=jnp.full((a,), -1, jnp.int32),
                    turn_srv=jnp.zeros((a,), jnp.int32),
                    wake_tmr=jnp.zeros((a,), jnp.int32),
                    wake_grp=jnp.zeros((a,), jnp.int32))
    if protocol == "lrsc":
        return dict(resv_core=jnp.full((a,), -1, jnp.int32),
                    resv_valid=jnp.zeros((a,), bool))
    if protocol == "amo_lock":
        return dict(lock=jnp.zeros((a,), bool))
    raise ValueError(f"reference has no protocol {protocol!r}")


def _fifo_queue_rule(protocol, P, bank, win, acq, rel, q):
    """colibri / lrscwait / mwait_lock: one FIFO of waiters per bank; the
    head holds the word.  Returns (bank, outcome, side messages)."""
    qbuf, qhead, qlen = bank["qbuf"], bank["qhead"], bank["qlen"]
    a = qhead.shape[0]
    rows = jnp.arange(a)
    room = qlen < q
    outcome = jnp.where(acq & (qlen == 0), GRANT,
                        jnp.where(acq & room, WAIT,
                                  jnp.where(acq, FAIL,
                                            jnp.where(rel, DONE, NONE))))
    put = acq & room
    qbuf = qbuf.at[jnp.where(put, rows, a), (qhead + qlen) % q].set(
        win, mode="drop")
    qlen = qlen + put - rel
    qhead = jnp.where(rel, (qhead + 1) % q, qhead)
    handoff = rel & (qlen > 0)
    delay = P["lat"] if protocol == "lrscwait" else P["lat"] + 2
    wake_tmr = jnp.where(handoff, delay, bank["wake_tmr"])
    msgs = jnp.zeros((a,), jnp.int32)
    if protocol == "colibri":        # SuccessorUpdate, WakeUpRequest
        msgs = 2 * ((outcome == WAIT) + handoff)
    elif protocol == "mwait_lock":   # Mwait set-up
        msgs = 2 * (outcome == WAIT)
    bank = dict(qbuf=qbuf, qhead=qhead, qlen=qlen, wake_tmr=wake_tmr)
    return bank, outcome, msgs


def _hier_rule(P, bank, win, acq, rel, n, geom):
    """colibri_hier: waiters queue per (bank, group of cores); groups with
    waiters queue per bank; a group's turn ends when its queue drains,
    or after gsz ops while another group waits."""
    G, gsz, cap = geom
    b = dict(bank)
    a = b["cur_grp"].shape[0]
    rows = jnp.arange(a)
    grp = jnp.minimum(jnp.minimum(win, n - 1) // gsz, G - 1)
    lq = rows * G + grp
    msgs = jnp.zeros((a,), jnp.int32)
    idle = b["cur_grp"] < 0
    grant = acq & idle
    b["cur_grp"] = jnp.where(grant, grp, b["cur_grp"])
    b["turn_srv"] = jnp.where(grant, 0, b["turn_srv"])
    wait = acq & ~idle
    slot = (b["lqhead"][lq] + b["lqlen"][lq]) % cap
    at = jnp.where(wait, lq, a * G)
    b["lqbuf"] = b["lqbuf"].at[at, slot].set(win, mode="drop")
    b["lqlen"] = b["lqlen"].at[at].add(1, mode="drop")
    msgs = msgs + wait
    reg = wait & (b["cur_grp"] != grp) & ~b["g_inq"][rows, grp]
    gslot = (b["gqhead"] + b["gqlen"]) % G
    b["ggq"] = b["ggq"].at[jnp.where(reg, rows, a), gslot].set(
        grp, mode="drop")
    b["gqlen"] = b["gqlen"] + reg
    b["g_inq"] = b["g_inq"].at[jnp.where(reg, rows, a), grp].set(
        True, mode="drop")
    msgs = msgs + 2 * reg
    # release by the turn-holding group
    served = b["turn_srv"] + 1
    spent = rel & (served >= gsz) & (b["gqlen"] > 0)
    local_left = b["lqlen"][lq] > 0
    more = rel & local_left & ~spent
    b["wake_grp"] = jnp.where(more, grp, b["wake_grp"])
    b["wake_tmr"] = jnp.where(more, 2, b["wake_tmr"])
    b["turn_srv"] = jnp.where(more, served, b["turn_srv"])
    msgs = msgs + more
    rereg = rel & local_left & spent
    tail = (b["gqhead"] + b["gqlen"]) % G
    b["ggq"] = b["ggq"].at[jnp.where(rereg, rows, a), tail].set(
        grp, mode="drop")
    b["gqlen"] = b["gqlen"] + rereg
    b["g_inq"] = b["g_inq"].at[jnp.where(rereg, rows, a), grp].set(
        True, mode="drop")
    msgs = msgs + 2 * rereg
    over = rel & (~local_left | spent)
    nxt = over & (b["gqlen"] > 0)
    ng = b["ggq"][rows, b["gqhead"]]
    b["cur_grp"] = jnp.where(nxt, ng, b["cur_grp"])
    b["g_inq"] = b["g_inq"].at[jnp.where(nxt, rows, a), ng].set(
        False, mode="drop")
    b["gqhead"] = jnp.where(nxt, (b["gqhead"] + 1) % G, b["gqhead"])
    b["gqlen"] = b["gqlen"] - nxt
    b["wake_grp"] = jnp.where(nxt, ng, b["wake_grp"])
    b["wake_tmr"] = jnp.where(nxt, P["lat"] + 2, b["wake_tmr"])
    b["turn_srv"] = jnp.where(nxt, 0, b["turn_srv"])
    msgs = msgs + 2 * nxt
    b["cur_grp"] = jnp.where(over & ~nxt, -1, b["cur_grp"])
    outcome = jnp.where(grant, GRANT, jnp.where(
        wait, WAIT, jnp.where(rel, DONE, NONE)))
    return b, outcome, msgs


def _bank_rule(protocol, P, bank, win, acq, rel, n, q):
    if protocol in ("colibri", "lrscwait", "mwait_lock"):
        return _fifo_queue_rule(protocol, P, bank, win, acq, rel, q)
    if protocol == "colibri_hier":
        return _hier_rule(P, bank, win, acq, rel, n, q)
    a = win.shape[0]
    zero = jnp.zeros((a,), jnp.int32)
    if protocol == "lrsc":
        rc, rv = bank["resv_core"], bank["resv_valid"]
        take = acq & ~rv
        owner = rel & rv & (rc == win)
        outcome = jnp.where(acq, GRANT, jnp.where(
            owner, DONE, jnp.where(rel, FAIL, NONE)))
        bank = dict(resv_core=jnp.where(take, win, rc),
                    resv_valid=(rv | take) & ~owner)
        return bank, outcome, zero
    if protocol == "amo_lock":
        lock = bank["lock"]
        outcome = jnp.where(acq & ~lock, GRANT, jnp.where(
            acq, FAIL, jnp.where(rel, DONE, NONE)))
        return dict(lock=(lock | acq) & ~rel), outcome, zero
    raise ValueError(protocol)


def _wake(protocol, bank, n, geom):
    """Fire the per-bank wake timers; returns (bank, woken core per bank
    or n, wake responses due next cycle)."""
    b = dict(bank)
    a = b["wake_tmr"].shape[0]
    rows = jnp.arange(a)
    fire = b["wake_tmr"] == 1
    b["wake_tmr"] = jnp.maximum(b["wake_tmr"] - 1, 0)
    if protocol == "colibri_hier":
        G, _, cap = geom
        wq = rows * G + b["wake_grp"]
        ok = fire & (b["lqlen"][wq] > 0)
        head = b["lqbuf"][wq, b["lqhead"][wq]]
        at = jnp.where(ok, wq, a * G)
        b["lqhead"] = b["lqhead"].at[at].add(1, mode="drop") % cap
        b["lqlen"] = b["lqlen"].at[at].add(-1, mode="drop")
    else:
        ok = fire & (b["qlen"] > 0)
        head = b["qbuf"][rows, b["qhead"]]
    return b, jnp.where(ok, head, n), (b["wake_tmr"] == 1).sum()


def simulate(point, dyn, broken=None):
    """One point.  ``point`` holds the static fields (protocol, workload,
    topology, n_cores, cycles, q_slots, n_groups, clusters, and any
    scalar kept static); ``dyn`` the traced scalars.  Returns the raw
    counters and final state as device arrays."""
    P = {**{k: point[k] for k in DYN if k in point}, **dyn}
    protocol, workload = point["protocol"], point["workload"]
    if protocol not in PROTOCOLS:
        raise ValueError(f"reference has no protocol {protocol!r}")
    if broken not in (None, "fifo", "latency"):
        raise ValueError(f"no control {broken!r}")
    n, cycles = point["n_cores"], point["cycles"]
    a = point["banks"]
    prog = PROGRAMS[workload]
    L = len(prog)
    is_bar = jnp.asarray([s[0] for s in prog])
    pre_len = jnp.asarray([s[1] for s in prog]) * P["work"] + jnp.asarray(
        [s[2] for s in prog])
    mode = jnp.asarray([s[3] for s in prog])
    na = P["n_addrs"]
    na_u = na if isinstance(na, int) else na.astype(jnp.uint32)
    fixed = (jnp.asarray([s[4] for s in prog]).astype(jnp.uint32)
             % na_u).astype(jnp.int32)
    mod_tab = jnp.asarray([s[5] for s in prog]) * P["modify"] + jnp.asarray(
        [s[6] for s in prog])
    has_bar = any(s[0] for s in prog)
    has_zipf = any(s[3] == ZIPF for s in prog)
    if protocol == "colibri_hier":
        q = _groups(point["n_groups"], n)
    elif protocol == "lrscwait":
        q = min(point["q_slots"], n)
    else:
        q = n
    exp_cap = 1 if protocol == "amo_lock" else P["backoff_exp"]
    hop_t, extra_t, cross_t = _topology(point["topology"],
                                        point["clusters"], n, a)
    levels = LEVELS[point["topology"]]
    core = jnp.arange(n, dtype=jnp.int32)
    hol_block = P["hol_block"]

    def target(opc, pc):
        h = mix(core * 7919 + opc * 104729 + P["seed"])
        out = jnp.where(mode[pc] == FIXED, fixed[pc],
                        (h % na_u).astype(jnp.int32))
        if has_zipf:
            out = jnp.where(mode[pc] == ZIPF,
                            zipf(h, P["n_addrs"], P["zipf_skew"]), out)
        return out

    def accept(req, rot, budget):
        # the `budget` requesters first in rotated priority order
        rank = jnp.zeros((n,), jnp.int32).at[rot].set(req.astype(jnp.int32))
        return req & (jnp.cumsum(rank)[rot] - 1 < budget)

    z = jnp.zeros((), jnp.int32)
    zn = jnp.zeros((n,), jnp.int32)
    s0 = dict(st=jnp.full((n,), WORK, jnp.int32),
              tmr=(core * 3) % (P["work"] + 1), addr=zn, phase=zn, pc=zn,
              bar_cnt=zn, nxt=zn, arr_cyc=jnp.full((n,), -1, jnp.int32),
              parked=jnp.zeros((n,), bool), resp_prev=z, opc=zn,
              streak=zn, ops=zn, acq_start=zn,
              bank=_init_bank(protocol, a, n, q),
              msgs=z, polls=z, addr_ops=jnp.zeros((a,), jnp.int32),
              sleep_cyc=z, bar_cyc=z,
              lat_hist=jnp.zeros((LAT_BINS,), jnp.int32), lat_max=z,
              backoff_cyc=z, active_cyc=z, bank_ops=z, net_stall=z,
              w_tmr=zn, w_served=zn)
    if levels:
        s0["hops"] = z

    def step(s, cyc):
        s = dict(s)
        st, pc = s["st"], s["pc"]
        tmr = jnp.maximum(s["tmr"] - 1, 0)
        due = tmr == 0
        # timers that ran out: local work -> acquire, backoff -> acquire
        # again, modify window -> release
        fresh_op = due & (st == WORK)
        retry = due & (st == BACKOFF)
        release = due & (st == MOD)
        send = fresh_op | retry | release
        addr = jnp.where(fresh_op, target(s["opc"], pc), s["addr"])
        phase = jnp.where(release, REL, jnp.where(fresh_op | retry, ACQ,
                                                  s["phase"]))
        st = jnp.where(send, REQ, st)
        flight = P["lat"]
        if levels:
            flight = flight + jnp.asarray(extra_t)[core, addr]
        tmr = jnp.where(send, flight, tmr)
        # a response arrived
        resp = due & (st == RESP)
        done = resp & (s["nxt"] == AFTER_DONE)
        to_bar = done & is_bar[pc]
        pc_new = jnp.where(done, (pc + 1) % L, pc)
        st = jnp.where(done, jnp.where(to_bar, BARWAIT, WORK), st)
        tmr = jnp.where(done & ~to_bar, pre_len[pc_new], tmr)
        pc = pc_new
        s["ops"] = s["ops"] + (done & (pc == 0))
        s["opc"] = s["opc"] + done
        s["bar_cnt"] = s["bar_cnt"] + to_bar
        acq_start = jnp.where(fresh_op, cyc, s["acq_start"])
        s["addr_ops"] = s["addr_ops"].at[addr].add(done.astype(jnp.int32))
        mod_len = mod_tab[pc]
        go_mod = resp & (s["nxt"] == AFTER_MOD)
        st = jnp.where(go_mod, MOD, st)
        tmr = jnp.where(go_mod, mod_len, tmr)
        go_back = resp & (s["nxt"] == AFTER_BACKOFF)
        st = jnp.where(go_back, BACKOFF, st)
        streak = jnp.where(go_back, jnp.minimum(s["streak"] + 1, exp_cap),
                           jnp.where(done, 0, s["streak"]))
        jitter = (mix(core + cyc) % 32).astype(jnp.int32)
        tmr = jnp.where(go_back, (P["backoff"] << jnp.maximum(streak - 1, 0))
                        + jitter, tmr)
        side = z
        if has_bar:
            # the last arrival releases every waiter, one message each
            free = (st == BARWAIT) & (s["bar_cnt"] <= s["bar_cnt"].min())
            st = jnp.where(free, WORK, st)
            tmr = jnp.where(free, P["lat"] + pre_len[pc], tmr)
            side = side + free.sum()
        # network: new requests take a link slot once, in rotating order
        new = (st == REQ) & (tmr == 0) & ~s["parked"]
        shift = (cyc * 97) % n
        rot = (core + shift) % n
        if isinstance(hol_block, int):
            hol = s["parked"].sum() // hol_block if hol_block else 0
        else:
            hol = jnp.where(hol_block > 0, s["parked"].sum()
                            // jnp.maximum(hol_block, 1), 0)
        ok = accept(new, rot, jnp.maximum(P["net_bw"] - s["resp_prev"]
                                          - hol, 1))
        for lv, (_, div) in enumerate(levels):
            xing = jnp.asarray(cross_t[lv])[core, addr]
            ok = ok & (~xing | accept(new & xing, rot, jnp.maximum(
                P["net_bw"] // div, 1)))
        s["net_stall"] = s["net_stall"] + (new & ~ok).sum()
        parked = s["parked"] | ok
        arr_cyc = jnp.where(ok, cyc, s["arr_cyc"])
        if levels:
            s["hops"] = s["hops"] + 2 * jnp.where(
                ok, jnp.asarray(hop_t)[core, addr], 0).sum()
        # banks: the oldest parked request wins (lowest rotated priority
        # among equals); the control serves the newest instead
        here = parked & (st == REQ)
        if broken != "fifo":
            first = jnp.full((a,), BIG, jnp.int32).at[addr].min(
                jnp.where(here, arr_cyc, BIG))
        else:
            first = jnp.full((a,), -1, jnp.int32).at[addr].max(
                jnp.where(here, arr_cyc, -1))
        tie = here & (arr_cyc == first[addr])
        best = jnp.full((a,), BIG, jnp.int32).at[addr].min(
            jnp.where(tie, rot, BIG))
        won = tie & (rot == best[addr])
        win = jnp.full((a,), n, jnp.int32).at[jnp.where(won, addr, a)].set(
            core, mode="drop")
        wc = jnp.minimum(win, n - 1)
        acq = (win < n) & (phase[wc] == ACQ)
        rel = (win < n) & (phase[wc] == REL)
        parked = parked & ~won
        arr_cyc = jnp.where(won, -1, arr_cyc)
        bank, outcome, pmsgs = _bank_rule(protocol, P, s["bank"], win, acq,
                                          rel, n, q)
        answer = (outcome == GRANT) | (outcome == DONE) | (outcome == FAIL)
        rtmr = P["lat"]               # every answer takes one latency
        at_w = lambda m: jnp.where(m, win, n)  # noqa: E731
        st = st.at[at_w(answer)].set(RESP, mode="drop")
        st = st.at[at_w(outcome == WAIT)].set(SLEEP, mode="drop")
        tmr = tmr.at[at_w(answer)].set(rtmr, mode="drop")
        follow = jnp.where(outcome == GRANT, AFTER_MOD, jnp.where(
            outcome == DONE, AFTER_DONE, AFTER_BACKOFF))
        nxt = s["nxt"].at[at_w(answer)].set(follow, mode="drop")
        s["polls"] = s["polls"] + (outcome == FAIL).sum()
        side = side + pmsgs.sum()
        s["bank_ops"] = s["bank_ops"] + won.sum()
        wake_load = z
        if protocol in QUEUE_PROTOCOLS:
            bank, woken, wake_load = _wake(protocol, bank, n, q)
            hit = jnp.zeros((n,), bool).at[woken].set(True, mode="drop")
            st = jnp.where(hit, MOD, st)
            tmr = jnp.where(hit, mod_len, tmr)
        s["msgs"] = s["msgs"] + 2 * won.sum() + side
        # completion latency of this cycle's retiring releases, counted
        # when the response lands inside the horizon
        lands = cyc + jnp.maximum(rtmr, 1)
        fin = (outcome == DONE) & (lands < cycles)
        lat = lands - acq_start[wc]
        if broken == "latency":
            lat = lat - P["lat"]
        bkt = jnp.clip((LAT_SUB * jnp.log2(lat.astype(jnp.float32) + 1.0)
                        ).astype(jnp.int32), 0, LAT_BINS - 1)
        s["lat_hist"] = s["lat_hist"].at[jnp.where(fin, bkt, LAT_BINS)].add(
            1, mode="drop")
        s["lat_max"] = jnp.maximum(s["lat_max"],
                                   jnp.max(jnp.where(fin, lat, 0)))
        s["resp_prev"] = (won.sum() + side + wake_load).astype(jnp.int32)
        s["sleep_cyc"] = s["sleep_cyc"] + (st == SLEEP).sum()
        s["bar_cyc"] = s["bar_cyc"] + (st == BARWAIT).sum()
        s["backoff_cyc"] = s["backoff_cyc"] + (st == BACKOFF).sum()
        s["active_cyc"] = s["active_cyc"] + ((st != SLEEP)
                                             & (st != BARWAIT)).sum()
        s.update(st=st, tmr=tmr, addr=addr, phase=phase, pc=pc, nxt=nxt,
                 arr_cyc=arr_cyc, parked=parked, streak=streak,
                 acq_start=acq_start, bank=bank)
        return s, None

    final, _ = lax.scan(step, s0, jnp.arange(cycles, dtype=jnp.int32))
    out = {k: v for k, v in final.items() if k != "bank"}
    out.update(final["bank"])
    return out


@partial(jax.jit, static_argnames=("point", "broken"))
def _batched(point, dyn, broken):
    pt = dict(point)
    return jax.vmap(lambda d: simulate(pt, d, broken))(dyn)


def run_points(points, broken=None):
    """Raw counters for a list of points (dicts of Spec fields plus
    ``banks``).  Points that differ only in the traced scalars share one
    compiled scan; each point's scalars listed in ``point["static"]``
    are folded as constants instead, as a single run folds them."""
    groups = {}
    for i, p in enumerate(points):
        keep = set(p.get("static", ()))
        key = tuple(sorted((k, v) for k, v in p.items() if k != "static"
                           and (k not in DYN or k in keep)))
        groups.setdefault(key, []).append(i)
    # dispatch every group before reading any back, so that tracing the
    # next group on the host overlaps the device running this one
    pending = []
    for static, idx in groups.items():
        names = [k for k in DYN if k not in dict(static)]
        dyn = {k: jnp.asarray([points[i][k] for i in idx], jnp.int32)
               for k in names}
        pending.append((idx, _batched(static, dyn, broken)))
    out = [None] * len(points)
    for idx, res in pending:
        res = jax.device_get(res)
        for j, i in enumerate(idx):
            out[i] = {k: np.asarray(v[j]) for k, v in res.items()}
    return out


# ---------------------------------------------------------------------------
# derived metrics, from the raw counters
# ---------------------------------------------------------------------------

def _pct(hist, q, lat_max):
    cum = np.cumsum(hist.astype(np.int64))
    total = int(cum[-1])
    if total == 0:
        return 0.0
    i = int(np.searchsorted(cum, max(int(math.ceil(q * total)), 1)))
    return float(min(2.0 ** ((i + 0.5) / LAT_SUB) - 1.0, lat_max))


def derive(raw, cycles, energy):
    """The metric set every result carries: throughput, per-core
    fairness, latency percentiles from the geometric histogram (clamped
    to the exact maximum), and pJ per op under the configuration's
    per-event energy model."""
    r = dict(raw)
    ops = r["ops"]
    x = ops.astype(np.float64)
    r["throughput"] = float(ops.sum()) / cycles
    r["fairness_min"] = float(ops.min()) / cycles
    r["fairness_max"] = float(ops.max()) / cycles
    sq = float((x * x).sum())
    r["jain_fairness"] = 0.0 if sq == 0.0 else float(x.sum()) ** 2 / (
        x.size * sq)
    lo, hi = float(x.min()), float(x.max())
    r["fairness_span"] = ((0.0 if hi <= 0.0 else math.inf) if lo <= 0.0
                          else hi / lo)
    lat_max = float(r["lat_max"])
    r["lat_p50"] = _pct(r["lat_hist"], 0.50, lat_max)
    r["lat_p95"] = _pct(r["lat_hist"], 0.95, lat_max)
    r["lat_max"] = lat_max
    e = energy
    f = {k: float(r[k]) for k in ("msgs", "bank_ops", "active_cyc",
                                  "sleep_cyc", "backoff_cyc", "bar_cyc")}
    n_ops = float(ops.sum())
    total = (e["e_msg"] * f["msgs"] + e["e_bank"] * f["bank_ops"]
             + e["e_active"] * (f["active_cyc"] - f["backoff_cyc"])
             + e["e_backoff"] * f["backoff_cyc"]
             + e["e_sleep"] * (f["sleep_cyc"] + f["bar_cyc"]))
    if "hops" in r:
        total += e["e_hop"] * float(r["hops"])
    r["energy_pj_per_op"] = total / max(n_ops, 1.0) if n_ops > 0 else 0.0
    return r
