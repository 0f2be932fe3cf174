"""Topology subsystem (``repro.core.topologies``): the hierarchical-NoC
registry, its compiled placement tables, and the engine's network-stage
integration.

The contract under test, in cost order:

* **the factored form rebuilds the tables** — the per-level core and
  bank cluster ids the engine consumes give back exactly the per-(core,
  bank) crossing masks, hop counts and extra latencies the placement
  hooks define, on both sides of the engine's select/gather crossover;
* **the tables are a lawful cover** — for every registered topology and
  any (n, a, clusters) shape, each (core, bank) pair gets exactly one
  hop path (the compile is deterministic and total), hop counts are odd
  (1 + 2 per crossed level), level crossings nest (crossing level l+1
  implies crossing level l — the pairing tree), and the extra latency
  is monotone in the hop count.  Property-tested with hypothesis when
  the container has it, and always with a seeded random sweep so the
  guarantee never silently disappears;
* **flat is free** — under ``topology="flat"`` the ``clusters`` knob is
  statically irrelevant: every protocol × workload point is
  bit-identical across cluster settings, and no ``hops`` stat appears;
* **both lookup forms are bit-identical to the table engine** — pinned
  goldens of ``hops``/``ops``/``lat_hist``/``net_stall``/``polls``
  recorded from the engine that gathered from (n·a) tables, and no
  per-lane gather from an (n·a) table in the scan body;
* **clusters are backend-agnostic** — the Pallas fused-step path never
  sees the topology (extra latency is billed once at issue, link caps
  run in the engine's network stage), so xla_cpu and pallas_interpret
  stay bit-identical on the hierarchical topologies too;
* **hop energy is additive** — ``energy_pj_per_op`` bills exactly
  ``e_hop × hops / ops`` on top of the flat decomposition;
* the windowed telemetry splits accepted traffic into intra- vs
  cross-cluster messages (zero cross-cluster under flat);
* ``nb_feb``'s full/empty bit tracks its queue (``feb == (qlen == 0)``)
  through grants, parks, and watchdog evictions — the invariant the
  model checker certifies, exercised here directly on the hooks.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols, topologies, workloads
from repro.core.protocols.base import (OUT_EVICT, OUT_GRANT, OUT_SLEEP,
                                       Ctx, FusedCtx)
from repro.core import sim
from repro.core.sim import SimParams, _run
from repro.core.topologies import LinkLevel, Topology, base as topo_base
from repro.core.topologies import registry as topo_registry
from repro.sync import Spec, run

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:          # container without hypothesis: the seeded
    given = None             # sweep below covers the same property


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contents_and_errors():
    assert set(topologies.names()) >= {"flat", "cluster2", "cluster3"}
    with pytest.raises(KeyError, match="registered"):
        topo_registry.get("no_such_topology")

    class Dup(Topology):
        name = "flat"

    with pytest.raises(ValueError, match="duplicate"):
        topo_registry.register(Dup)

    class Anon(Topology):
        pass

    with pytest.raises(ValueError, match="no name"):
        topo_registry.register(Anon)


def test_link_level_validation():
    with pytest.raises(ValueError, match="extra_lat"):
        LinkLevel("bad", extra_lat=-1, bw_div=1)
    with pytest.raises(ValueError, match="bw_div"):
        LinkLevel("bad", extra_lat=0, bw_div=0)


def test_spec_routes_topology():
    s = Spec(protocol="colibri", topology="cluster2", clusters=8)
    p = s.to_params()
    assert p.topology == "cluster2" and p.clusters == 8
    assert Spec.from_json(s.to_json()) == s
    assert s.replace(topology="flat").to_params().topology == "flat"
    with pytest.raises(ValueError):
        SimParams(protocol="colibri", topology="no_such_topology",
                  n_cores=8, cycles=100)


# ---------------------------------------------------------------------------
# placement tables: a lawful permutation-free cover
# ---------------------------------------------------------------------------

def _check_tables(topo, clusters: int, n: int, a: int) -> None:
    """The full table lawfulness property for one (topology, shape)."""
    p = types.SimpleNamespace(clusters=clusters)
    t = topo.tables(p, n, a)
    t2 = topo.tables(p, n, a)
    # exactly one path per (core, bank): the compile is a deterministic
    # total function of the shape — every pair covered, never two answers
    assert t.hops.shape == t.extra.shape == (n, a)
    np.testing.assert_array_equal(t.hops, t2.hops)
    np.testing.assert_array_equal(t.extra, t2.extra)
    assert len(t.cross) == len(topo.levels)
    # hop law: 1 + 2 per crossed level, so always odd and >= 1
    crossings = sum((x.astype(np.int64) for x in t.cross),
                    np.zeros((n, a), np.int64))
    np.testing.assert_array_equal(t.hops, 1 + 2 * crossings)
    assert (t.hops >= 1).all() and ((t.hops - 1) % 2 == 0).all()
    # extra law: per-level latencies of exactly the crossed levels
    want = sum((lv.extra_lat * x.astype(np.int64)
                for lv, x in zip(topo.levels, t.cross)),
               np.zeros((n, a), np.int64))
    np.testing.assert_array_equal(t.extra, want)
    assert (t.extra >= 0).all() and (t.extra[t.hops == 1] == 0).all()
    # nesting: crossing an outer level implies crossing every inner one
    for inner, outer in zip(t.cross, t.cross[1:]):
        assert (~outer | inner).all(), "level crossings must nest"
    # monotone: same hop count => same extra; more hops => >= extra
    by_hops = {}
    for h, e in zip(t.hops.ravel().tolist(), t.extra.ravel().tolist()):
        by_hops.setdefault(h, set()).add(e)
    assert all(len(v) == 1 for v in by_hops.values())
    ladder = [next(iter(by_hops[h])) for h in sorted(by_hops)]
    assert ladder == sorted(ladder)
    # placement ids stay in range
    assert t.core_cluster.shape == (n,) and t.bank_cluster.shape == (a,)
    assert (0 <= t.core_cluster).all()
    assert (t.core_cluster < max(1, min(clusters, n))).all()
    assert (0 <= t.bank_cluster).all()
    assert (t.bank_cluster < max(1, min(clusters, max(a, 1)))).all()
    assert t.is_flat == (not topo.levels)
    if t.is_flat:
        assert (t.hops == 1).all() and (t.extra == 0).all()
    # factoring: the per-level ids the engine consumes rebuild, pair by
    # pair, what the placement hooks define
    assert len(t.core_lvl) == len(t.bank_lvl) == len(topo.levels)
    assert t.extra_lat == tuple(lv.extra_lat for lv in topo.levels)
    cc, bc = topo.core_clusters(p, n), topo.bank_clusters(p, a)
    hops, extra = np.ones((n, a), np.int64), np.zeros((n, a), np.int64)
    for lv, (cl, bl, x) in enumerate(zip(t.core_lvl, t.bank_lvl, t.cross)):
        assert cl.shape == (n,) and bl.shape == (a,)
        want = (topo.level_cluster(cc, lv, p)[:, None]
                != topo.level_cluster(bc, lv, p)[None, :])
        np.testing.assert_array_equal(cl[:, None] != bl[None, :], want)
        np.testing.assert_array_equal(x, want)
        hops += 2 * want
        extra += topo.levels[lv].extra_lat * want
    np.testing.assert_array_equal(t.hops, hops)
    np.testing.assert_array_equal(t.extra, extra)


def test_tables_property_seeded_sweep():
    rng = np.random.default_rng(20240808)
    shapes = [(2, 1, 1), (2, 1, 2), (4, 2, 2), (5, 3, 2), (16, 4, 4),
              (33, 7, 4), (64, 16, 8), (256, 16, 4)]
    # both sides of the engine's select/gather crossover
    x = sim._TOPO_SELECT_BANKS
    shapes += [(64, x, 4), (64, x + 1, 8), (96, 2 * x + 3, 16),
               (1024, 4, 4), (128, 8 * x, 32)]
    shapes += [(int(rng.integers(2, 129)), int(rng.integers(1, 33)),
                int(rng.integers(1, 17))) for _ in range(40)]
    for n, a, clusters in shapes:
        for name in topologies.names():
            _check_tables(topo_registry.get(name), clusters, n, a)


if given is not None:
    @given(st.integers(2, 256), st.integers(1, 64), st.integers(1, 32),
           st.sampled_from(["flat", "cluster2", "cluster3"]))
    @settings(max_examples=80, deadline=None)
    def test_tables_property_hypothesis(n, a, clusters, name):
        _check_tables(topo_registry.get(name), clusters, n, a)


def test_block_placement_matches_hw_event_geometry():
    """cluster_of must agree with the hw_event protocol's group split,
    so the event unit a core registers with IS its topology cluster."""
    from repro.core.protocols.hw_event import HwEvent
    for n, clusters in ((8, 2), (16, 4), (13, 4), (7, 8)):
        p = types.SimpleNamespace(topology="cluster2", clusters=clusters,
                                  n_groups=999)
        g, gsz, _ = HwEvent._geom(p, n)
        cc = topo_base.cluster_of(np.arange(n), n, clusters)
        np.testing.assert_array_equal(
            cc, np.minimum(np.arange(n) // gsz, g - 1))


# ---------------------------------------------------------------------------
# flat is free: clusters statically irrelevant, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("protocol", protocols.names())
def test_flat_bit_identical_across_cluster_knob(protocol):
    """Full protocol × workload grid: under topology="flat" the
    clusters knob (a static recompile) must not move a single bit, and
    no hops stat may appear."""
    for wl in workloads.names():
        base = dict(protocol=protocol, workload=wl, n_cores=16,
                    n_addrs=4, cycles=700)
        r1 = _run(SimParams(clusters=1, **base))
        r4 = _run(SimParams(clusters=4, **base))
        assert "hops" not in r1 and "hops" not in r4
        assert set(r1) == set(r4)
        for k in sorted(r1):
            np.testing.assert_array_equal(
                np.asarray(r1[k]), np.asarray(r4[k]),
                err_msg=f"{protocol}/{wl}: field {k!r} diverged")


# ---------------------------------------------------------------------------
# the engine's per-cycle lookup: both forms bit-identical to the tables
# ---------------------------------------------------------------------------

# recorded from the engine that gathered hops/extra/cross at
# ``iota * a + addr`` from flattened (n·a) tables (zipf_histogram,
# net_bw=8, so the link budgets bite); lat_hist as {bin: count}
LOOKUP_GOLDEN = {
    "select": (
        dict(protocol="colibri_hier", topology="cluster2", clusters=4,
             n_addrs=4, seed=11),
        dict(hops=3420, net_stall=550, polls=0,
             ops=[4, 4, 10, 8, 6, 3, 3, 10, 8, 5, 3, 11, 10, 7, 5, 4, 3, 9,
                  6, 4, 3, 7, 8, 6, 3, 3, 9, 8, 5, 3, 3, 9, 7, 4, 2, 1, 8, 6,
                  4, 1, 1, 8, 6, 3, 1, 1, 8, 5, 2, 2, 8, 6, 4, 2, 2, 8, 5, 4,
                  1, 2, 7, 4, 3, 2],
             lat_hist={17: 4, 18: 6, 19: 2, 20: 6, 21: 54, 22: 16, 23: 31,
                       24: 10, 25: 22, 26: 20, 27: 7, 28: 14, 29: 8, 30: 15,
                       31: 23, 32: 16, 33: 14, 34: 2, 35: 2, 36: 12, 37: 3,
                       38: 17, 39: 14})),
    "gather": (
        dict(protocol="colibri", topology="cluster3", clusters=8,
             n_addrs=256, seed=12),
        dict(hops=10362, net_stall=5369, polls=0,
             ops=[8, 6, 3, 12, 10, 7, 5, 14, 11, 9, 7, 15, 13, 11, 8, 9, 4,
                  9, 10, 8, 11, 3, 12, 9, 10, 5, 14, 11, 9, 7, 12, 4, 11, 8,
                  6, 13, 6, 10, 7, 12, 3, 12, 9, 7, 5, 14, 11, 9, 6, 4, 13,
                  11, 8, 6, 15, 12, 10, 7, 12, 6, 12, 9, 8, 13],
             lat_hist={18: 57, 19: 4, 20: 35, 21: 13, 22: 6, 23: 229, 24: 51,
                       25: 87, 26: 27, 27: 20, 28: 5, 29: 4, 30: 2, 32: 2,
                       33: 5, 34: 7, 35: 4, 36: 5, 37: 7, 38: 5, 39: 6})),
}


@pytest.mark.parametrize("form", sorted(LOOKUP_GOLDEN))
def test_lookup_forms_match_table_goldens(form):
    cfg, want = LOOKUP_GOLDEN[form]
    p = SimParams(workload="zipf_histogram", n_cores=64, cycles=1500,
                  net_bw=8, **cfg)
    assert sim.topo_lookup(p) == form
    r = _run(p)
    lh = np.asarray(r["lat_hist"])
    got = dict(hops=int(r["hops"]), net_stall=int(r["net_stall"]),
               polls=int(r["polls"]), ops=np.asarray(r["ops"]).tolist(),
               lat_hist={int(i): int(lh[i]) for i in np.flatnonzero(lh)})
    assert got == want


def _scan_body_gathers(p):
    """(operand size, result size) of every gather in the scan body."""
    jx = jax.make_jaxpr(lambda: sim.simulate(p))()
    body = next(e for e in jx.jaxpr.eqns
                if e.primitive.name == "scan").params["jaxpr"].jaxpr

    def walk(j):
        for e in j.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
    return [(e.invars[0].aval.size, e.outvars[0].aval.size)
            for e in walk(body) if e.primitive.name == "gather"]


@pytest.mark.parametrize("topology,a,form",
                         [("cluster2", 4, "select"),
                          ("cluster3", 2 * sim._TOPO_SELECT_BANKS, "gather")])
def test_scan_body_has_no_per_lane_table_gather(topology, a, form):
    """The per-(core, bank) tables are never gathered per lane again:
    no gather in the scan body reads an operand of n·a elements into n
    lanes (the table engine had three for cluster2, four for cluster3);
    the gather form reads one (a,) table per level."""
    n = 1024
    p = SimParams(protocol="colibri_hier", topology=topology, clusters=8,
                  n_cores=n, n_addrs=a, cycles=8)
    assert sim.topo_lookup(p) == form
    gathers = _scan_body_gathers(p)
    assert (n * a, n) not in gathers
    levels = len(topologies.get(topology).levels)
    flat = _scan_body_gathers(dataclasses.replace(p, topology="flat"))
    extra = [g for g in gathers if g == (a, n)]
    assert len(extra) - flat.count((a, n)) == (
        levels if form == "gather" else 0)


# ---------------------------------------------------------------------------
# hierarchical topologies: backend parity and engine effects
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("protocol,topology",
                         [("colibri", "cluster2"),
                          ("lrscwait", "cluster2"),
                          ("hw_event", "cluster2"),
                          ("nb_feb", "cluster2"),
                          ("colibri_hier", "cluster3")])
def test_cluster_backend_parity(protocol, topology):
    """xla_cpu and pallas_interpret stay bit-identical per topology —
    the kernel never sees the tables (billed at issue / network stage)."""
    res = {}
    for backend in ("xla_cpu", "pallas_interpret"):
        res[backend] = _run(SimParams(
            protocol=protocol, workload="zipf_histogram", backend=backend,
            topology=topology, clusters=4, n_cores=32, n_addrs=4,
            cycles=900))
    a, b = res["xla_cpu"], res["pallas_interpret"]
    assert set(a) == set(b)
    for k in sorted(a):
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]),
            err_msg=f"{protocol}/{topology}: field {k!r} diverged")
    assert int(a["hops"]) > 0 and int(a["ops"].sum()) > 0


def test_cluster_slows_contention_and_counts_hops():
    """Hierarchical latency + per-level link caps must cost throughput
    on a contended workload, and every remote acceptance adds hops."""
    base = dict(protocol="colibri", workload="zipf_histogram", n_cores=32,
                n_addrs=4, cycles=1500, zipf_skew=200)
    flat = _run(SimParams(**base))
    c2 = _run(SimParams(topology="cluster2", clusters=4, **base))
    c3 = _run(SimParams(topology="cluster3", clusters=8, **base))
    assert "hops" not in flat
    assert int(c2["hops"]) > 0 and int(c3["hops"]) > 0
    assert int(flat["ops"].sum()) > int(c2["ops"].sum()) > 0
    assert int(c2["ops"].sum()) >= int(c3["ops"].sum()) > 0


def test_hop_energy_is_additive():
    """energy_per_op with a hops stat = flat decomposition + e_hop·hops
    per op, exactly."""
    from repro.core import costmodel, metrics
    res = _run(SimParams(protocol="colibri", workload="zipf_histogram",
                         topology="cluster2", clusters=4, n_cores=32,
                         n_addrs=4, cycles=900))
    stats = metrics.energy_stats(res)
    assert stats["hops"] > 0
    fit = costmodel.default_fit()
    with_hops = costmodel.energy_per_op(stats, fit)
    without = costmodel.energy_per_op(
        {k: v for k, v in stats.items() if k != "hops"}, fit)
    np.testing.assert_allclose(
        with_hops - without, fit.e_hop * stats["hops"] / stats["ops"],
        rtol=1e-12)


def test_noc_telemetry_splits_local_and_cross_cluster():
    base = dict(protocol="colibri", workload="zipf_histogram", n_cores=32,
                n_addrs=4, cycles=1200, telemetry_windows=12,
                zipf_skew=150)
    flat = run(Spec(**base)).timeseries()
    c2 = run(Spec(topology="cluster2", clusters=4, **base)).timeseries()
    assert flat.counts("xcl_msgs").sum() == 0
    assert flat.counts("loc_msgs").sum() > 0
    assert c2.counts("xcl_msgs").sum() > 0
    assert c2.counts("loc_msgs").sum() > 0
    # the named accessors are per-cycle rates over the same windows
    assert c2.cross_cluster_msgs.shape == (c2.n_used,)
    assert (c2.local_msgs >= 0).all()


def test_perfetto_noc_counter_track(tmp_path):
    import json

    from repro import obs
    r = run(Spec(protocol="colibri", workload="zipf_histogram",
                 topology="cluster2", clusters=4, n_cores=16, n_addrs=4,
                 cycles=800, record_trace=True, telemetry_windows=8,
                 zipf_skew=150))
    path = obs.perfetto.export(r, tmp_path / "noc.json")
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    noc = [e for e in evs if e["ph"] == "C" and e["name"] == "link msgs"]
    assert noc, "telemetry-backed NoC counter track missing"
    assert sum(e["args"]["cross_cluster"] for e in noc) > 0
    assert sum(e["args"]["local"] for e in noc) > 0


# ---------------------------------------------------------------------------
# nb_feb: the full/empty bit tracks the queue
# ---------------------------------------------------------------------------

def _feb_ok(bank) -> bool:
    return bool(np.asarray(bank["feb"])[0]) == \
        (int(np.asarray(bank["qlen"])[0]) == 0)


def test_nb_feb_bit_tracks_queue_through_eviction():
    """feb == (qlen == 0) after every grant, park, and watchdog
    eviction — including draining the queue by evicting dead cores,
    where a stale empty bit would deadlock the bank forever."""
    proto = protocols.get("nb_feb")
    p = SimParams(protocol="nb_feb", n_cores=3, n_addrs=1, cycles=100)
    n, a = 3, 1
    q_cap = proto.q_cap(p, n)
    bank = proto.init_bank_state(p, a, n, q_cap)
    assert bool(np.asarray(bank["feb"])[0]) and _feb_ok(bank)
    expect = [OUT_GRANT, OUT_SLEEP, OUT_SLEEP]
    for c in range(n):
        fx = FusedCtx(p=p, n=n, a=a, q_cap=q_cap,
                      win=jnp.asarray([c], jnp.int32),
                      acq_b=jnp.asarray([True]),
                      rel_b=jnp.asarray([False]))
        bank, fo = proto.fused_access(fx, dict(bank))
        assert int(fo.kind[0]) == expect[c]
        assert _feb_ok(bank)
    assert int(np.asarray(bank["qlen"])[0]) == 3
    # every core dies; the watchdog evicts the head one timeout at a
    # time until the bank drains — the bit must flip full again exactly
    # when the queue empties
    z = jnp.zeros((n,), bool)
    zb = jnp.zeros((a,), bool)
    ctx = Ctx(p=p, n=n, a=a, q_cap=q_cap, is_acq=z, is_rel=z,
              wa=jnp.zeros((n,), jnp.int32),
              wc=jnp.arange(n, dtype=jnp.int32),
              ba=jnp.arange(a, dtype=jnp.int32),
              win_core=jnp.full((a,), n, jnp.int32), acq_b=zb, rel_b=zb,
              mod_dur=jnp.ones((n,), jnp.int32))
    cs = dict(st=jnp.zeros((n,), jnp.int32), tmr=jnp.zeros((n,), jnp.int32),
              nxt=jnp.full((n,), -1, jnp.int32),
              polls=jnp.zeros((), jnp.int32), msgs=jnp.zeros((), jnp.int32))
    killed = jnp.ones((n,), bool)
    for left in (2, 1, 0):
        cs, bank, kind = proto.on_timeout(
            ctx, cs, dict(bank), jnp.asarray([True]), killed,
            jnp.asarray([0], jnp.int32))
        assert int(kind[0]) == OUT_EVICT
        assert int(np.asarray(bank["qlen"])[0]) == left
        assert _feb_ok(bank)
    assert bool(np.asarray(bank["feb"])[0])
