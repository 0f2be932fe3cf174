"""colibri_hier's bank state with banks on the last (lane) axis.

Every per-(bank, group) array keeps the bank on its last axis, so the
engine-step kernel tiles it ``(r, block_a)`` and a bank lane reads and
writes its own group's queue by a select over the rows of its own column.
Here, at 512 banks (two kernel tiles of 256) and 64 cores in 4 groups, a
scripted sequence of acquires and releases drives enqueues, global
registrations, a re-registration and turn hand-offs onto banks of both
tiles, and after every step the tiled kernel (interpreted), its dense
oracle ``fused_step_ref`` and the XLA scan path's ``on_access`` hold the
same whole bank state.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols, sim
from repro.core.protocols.base import (NXT_MOD, NXT_WORK_DONE, OUT_DONE,
                                       OUT_GRANT, OUT_SLEEP, P_ACQ, P_REL,
                                       RESP, SLEEP, Ctx)
from repro.kernels.engine_step.kernel import fused_step_call
from repro.kernels.engine_step.ops import PREF_BLOCK_A
from repro.kernels.engine_step.ref import _BIG, fused_step_ref

N, A, G = 64, 512, 4
GSZ = N // G
B0, B1, B4, B2, B3 = 5, 130, 200, 300, 509        # tile 0: B0, B1, B4

# one request per bank per step: (core, bank, phase); group = core // 16
STEPS = [
    # grants: each bank's first acquire takes the turn for its group
    [(0, B0, P_ACQ), (20, B1, P_ACQ), (10, B4, P_ACQ), (40, B2, P_ACQ),
     (60, B3, P_ACQ)],
    # enqueues: in the serving group (B0), or registering a new group
    [(1, B0, P_ACQ), (36, B1, P_ACQ), (30, B4, P_ACQ), (17, B2, P_ACQ),
     (2, B3, P_ACQ)],
    # more: a second registration (B0), a group already registered (B3)
    [(50, B0, P_ACQ), (21, B1, P_ACQ), (41, B2, P_ACQ), (3, B3, P_ACQ)],
    # releases: local wakes (B0, B2), budget spent with a competitor so
    # the group re-registers and hands over (B1), local queue drained so
    # the turn passes to the next registered group (B4, B3)
    [(0, B0, P_REL), (20, B1, P_REL), (10, B4, P_REL), (40, B2, P_REL),
     (60, B3, P_REL)],
]
KINDS = [
    {B0: OUT_GRANT, B1: OUT_GRANT, B4: OUT_GRANT, B2: OUT_GRANT,
     B3: OUT_GRANT},
    {B0: OUT_SLEEP, B1: OUT_SLEEP, B4: OUT_SLEEP, B2: OUT_SLEEP,
     B3: OUT_SLEEP},
    {B0: OUT_SLEEP, B1: OUT_SLEEP, B2: OUT_SLEEP, B3: OUT_SLEEP},
    {B0: OUT_DONE, B1: OUT_DONE, B4: OUT_DONE, B2: OUT_DONE, B3: OUT_DONE},
]


@pytest.fixture(scope="module")
def setup():
    p = sim.SimParams(protocol="colibri_hier", n_cores=N, n_addrs=A,
                      n_groups=G, cycles=100)
    proto = protocols.get("colibri_hier")
    q_cap = proto.q_cap(p, N)
    return p, proto, q_cap, proto.init_bank_state(p, A, N, q_cap)


def _inputs(step, cyc):
    cand = np.full(N, _BIG, np.int32)
    addr = np.zeros(N, np.int32)
    phase = np.zeros(N, np.int32)
    for c, b, ph in step:
        cand[c], addr[c], phase[c] = cyc, b, ph
    return dict(cand_cyc=jnp.asarray(cand), rot=jnp.arange(N, dtype=jnp.int32),
                addr=jnp.asarray(addr), phase=jnp.asarray(phase),
                acq_start=jnp.zeros(N, jnp.int32))


def _on_access(p, proto, q_cap, bank, ins, out):
    """The XLA scan path's bank update for the same winners."""
    valid, win = out["valid"], out["win"]
    phase, addr = ins["phase"], ins["addr"]
    wcs = jnp.minimum(win, N - 1)
    winner = jnp.zeros(N, bool).at[jnp.where(valid, win, N)].set(
        True, mode="drop")
    ctx = Ctx(p=sim._resolve(p), n=N, a=A, q_cap=q_cap,
              is_acq=winner & (phase == P_ACQ),
              is_rel=winner & (phase == P_REL), wa=addr,
              wc=jnp.arange(N, dtype=jnp.int32),
              ba=jnp.arange(A, dtype=jnp.int32), win_core=win,
              acq_b=valid & (phase[wcs] == P_ACQ),
              rel_b=valid & (phase[wcs] == P_REL),
              mod_dur=jnp.ones(N, jnp.int32))
    cs = dict(st=jnp.zeros(N, jnp.int32), tmr=jnp.zeros(N, jnp.int32),
              nxt=jnp.full(N, -1, jnp.int32), polls=jnp.zeros((), jnp.int32),
              msgs=jnp.zeros((), jnp.int32))
    return proto.on_access(ctx, cs, dict(bank))


def _assert_banks_equal(got, want, what):
    assert set(got) == set(want)
    for k in sorted(want):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=f"{what}: bank[{k!r}]")


@pytest.mark.parametrize("a", [4, 256, 512])
def test_bank_arrays_keep_banks_on_the_last_axis(a):
    p = sim.SimParams(protocol="colibri_hier", n_cores=N, n_addrs=a,
                      n_groups=G)
    proto = protocols.get("colibri_hier")
    assert proto.bank_last
    bank = proto.init_bank_state(p, a, N, proto.q_cap(p, N))
    for k, v in bank.items():
        assert v.shape[-1] == a, (k, v.shape)
    # the Result carries the flat layout: queue (b, g) is row b*G + g
    flat = proto.result_bank(bank)
    assert flat["lqhead"].shape == flat["lqlen"].shape == (a * G,)
    assert flat["lqbuf"].shape == (a * G, GSZ)
    assert flat["ggq"].shape == flat["g_inq"].shape == (a, G)


def test_kernel_tiles_hold_the_oracle_and_the_scan_path(setup):
    p, proto, q_cap, bank0 = setup
    assert A // PREF_BLOCK_A == 2
    kern = ref = xla = dict(bank0)
    for i, (step, kinds) in enumerate(zip(STEPS, KINDS)):
        if i == 3:
            # B1's turn has served all but one of its budget: the release
            # exhausts it while group 2 waits registered
            kern, ref, xla = (dict(b, turn_srv=b["turn_srv"].at[B1].set(
                GSZ - 1)) for b in (kern, ref, xla))
        ins = _inputs(step, cyc=10 + i)
        kw = dict(core={}, cyc=10 + i, shift=0, lat=p.lat, n=N, a=A,
                  q_cap=q_cap, cycles=p.cycles, **ins)
        out_k = fused_step_call(proto, p, kern, interpret=True,
                                block_a=PREF_BLOCK_A, block_n=N, **kw)
        out_r = fused_step_ref(proto, p, ref, **kw)
        cs, xla = _on_access(p, proto, q_cap, xla, ins, out_r)
        kern, ref = out_k["bank"], out_r["bank"]
        for f in ("valid", "win", "kind", "tmr", "msgs"):
            np.testing.assert_array_equal(np.asarray(out_k[f]),
                                          np.asarray(out_r[f]), err_msg=f)
        _assert_banks_equal(kern, ref, f"step {i} kernel vs oracle")
        _assert_banks_equal(xla, ref, f"step {i} scan path vs oracle")
        assert int(cs["msgs"]) == int(out_r["msgs"])
        kind = np.asarray(out_k["kind"])
        assert {b: int(kind[b]) for b in kinds} == kinds
        assert int((kind != 0).sum()) == len(kinds)
        for c, b, _ in step:
            want = {OUT_GRANT: (RESP, NXT_MOD), OUT_SLEEP: (SLEEP, -1),
                    OUT_DONE: (RESP, NXT_WORK_DONE)}[kinds[b]]
            assert (int(cs["st"][c]), int(cs["nxt"][c])) == want

    # what the script drove, read from the flat view (queue b*G + g)
    flat = {k: np.asarray(v) for k, v in proto.result_bank(kern).items()}
    lqlen = flat["lqlen"].reshape(A, G)
    # B0: group 0 woke core 1, group 3 registered behind it
    assert lqlen[B0].tolist() == [1, 0, 0, 1]
    assert flat["wake_grp"][B0] == 0 and flat["cur_grp"][B0] == 0
    assert flat["gqlen"][B0] == 1 and flat["g_inq"][B0].tolist() == [
        False, False, False, True]
    # B1: group 1 spent its budget with core 21 waiting: it re-registered
    # and handed the turn to group 2, whose core 36 wakes
    assert flat["cur_grp"][B1] == 2 and flat["wake_grp"][B1] == 2
    assert flat["gqlen"][B1] == 1
    assert flat["ggq"][B1][flat["gqhead"][B1]] == 1
    assert flat["g_inq"][B1].tolist() == [False, True, False, False]
    # B4 (tile 0) and B3 (tile 1): drained, the turn passed on
    assert flat["cur_grp"][B4] == 1 and flat["wake_grp"][B4] == 1
    assert flat["cur_grp"][B3] == 0 and flat["wake_grp"][B3] == 0
    assert flat["gqlen"][B3] == 0 and not flat["g_inq"][B3].any()
    assert lqlen[B3].tolist() == [2, 0, 0, 0]
    # B2 (tile 1): group 2's local wake, group 1 still registered
    assert flat["cur_grp"][B2] == 2 and flat["wake_grp"][B2] == 2
    assert lqlen[B2].tolist() == [0, 1, 1, 0]
    # the enqueued cores sit in their group's queue of their bank
    lqbuf = flat["lqbuf"].reshape(A, G, -1)
    assert lqbuf[B3, 0, :2].tolist() == [2, 3]
    assert lqbuf[B0, 3, 0] == 50 and lqbuf[B2, 1, 0] == 17
