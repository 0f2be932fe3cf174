"""The program's own instrumentation: stage scopes in the engine's scan
body and host spans around ``repro.sync.run`` and the Study path.

Scopes are op-name metadata only: with and without them the engine
traces to the same jaxpr, the same scan carries and the same results on
every backend.  Spans mark a profiler trace and, while a
``RunReport`` collects, add their wall time to ``RunReport.spans``;
the Study's chunk records take their dispatch and drain times from the
spans.
"""
import contextlib

import jax
import numpy as np
import pytest

from repro import obs
from repro.analysis import trace_safety
from repro.core import sim
from repro.core import sweep as sweep_mod
from repro.sync import Spec, Study, run

# the first golden point of tests/test_protocols.py, colibri's values
GOLDEN_POINT = dict(protocol="colibri", n_cores=64, n_addrs=1, cycles=3000,
                    seed=1)
GOLDEN_OPS, GOLDEN_MSGS, GOLDEN_SLEEP = 196, 1818, 183939


def _params(**kw):
    return sim.SimParams(protocol="colibri_hier", n_cores=32, n_addrs=4,
                         cycles=200, topology="cluster2", clusters=4,
                         backend="xla_cpu", **kw)


def _lowered_text(p):
    return jax.jit(lambda: sim.simulate(p)).lower().as_text(
        debug_info=True)


def test_scopes_reach_the_lowered_program():
    text = _lowered_text(_params())
    for stage in ("issue", "retire", "network", "arbitrate", "wake",
                  "account"):
        assert f"sim.{stage}" in text, stage


@pytest.mark.parametrize("kw", [
    {}, {"telemetry_windows": 8},
    {"faults": {"n_kill": 1, "kill_cyc": 50, "kill_holder": 1,
                "watchdog_cyc": 32, "msg_drop_bp": 100}},
], ids=["plain", "telemetry", "faults"])
def test_scopes_change_neither_jaxpr_nor_carries(kw, monkeypatch):
    p = _params(**kw)
    scoped = str(trace_safety.engine_jaxpr(p))
    carries = trace_safety.scan_carry_count(p)
    written = carries - len(trace_safety.read_only_carries(p))
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        assert "sim.network" not in _lowered_text(p)
        assert str(trace_safety.engine_jaxpr(p)) == scoped
        assert trace_safety.scan_carry_count(p) == carries
        assert (trace_safety.scan_carry_count(p)
                - len(trace_safety.read_only_carries(p))) == written


@pytest.mark.parametrize("backend", ["xla_cpu", "pallas_interpret"])
def test_golden_point_bit_identical_with_and_without_scopes(backend,
                                                            monkeypatch):
    p = sim.SimParams(backend=backend, **GOLDEN_POINT)
    scoped = jax.device_get(sim._run(p))
    assert int(scoped["ops"].sum()) == GOLDEN_OPS
    assert int(scoped["msgs"]) == GOLDEN_MSGS
    assert int(scoped["sleep_cyc"]) == GOLDEN_SLEEP
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = jax.device_get(jax.jit(lambda: sim.simulate(p))())
    assert set(plain) == set(scoped)
    for k in scoped:
        np.testing.assert_array_equal(plain[k], scoped[k], err_msg=k)


def test_golden_point_identical_across_backends():
    out = [jax.device_get(sim._run(sim.SimParams(backend=b,
                                                  **GOLDEN_POINT)))
           for b in ("xla_cpu", "pallas_interpret")]
    assert set(out[0]) == set(out[1])
    for k in out[0]:
        np.testing.assert_array_equal(out[0][k], out[1][k], err_msg=k)


# ---- host spans ------------------------------------------------------------
def test_span_without_report_records_nothing():
    assert obs.current() is None
    idle = obs.RunReport()                   # exists, but is not collecting
    with obs.span("repro.test", chunk=3) as took:
        pass
    assert took.seconds >= 0.0
    assert idle.spans == {}


def test_span_records_into_explicit_and_ambient_reports():
    mine = obs.RunReport()
    with obs.collect() as ambient:
        with obs.span("repro.a"):
            pass
        with obs.span("repro.b", mine):
            pass
        with obs.span("repro.a"):
            pass
    assert set(ambient.spans) == {"repro.a"}
    assert ambient.spans["repro.a"][1] == 2
    assert mine.spans == {"repro.b": [mine.spans["repro.b"][0], 1]}


def test_span_times_a_raising_block():
    with obs.collect() as rep:
        with pytest.raises(RuntimeError):
            with obs.span("repro.fails") as took:
                raise RuntimeError("boom")
    assert rep.spans["repro.fails"] == [took.seconds, 1]


def test_sync_run_spans():
    with obs.collect() as rep:
        r = run(Spec(protocol="colibri", n_cores=16, costs={"cycles": 300}))
    assert r.ok
    for name in ("repro.run", "repro.run.dispatch", "repro.run.fetch",
                 "repro.run.metrics"):
        assert rep.spans[name][1] == 1, name
    inner = sum(rep.spans[f"repro.run.{k}"][0]
                for k in ("dispatch", "fetch", "metrics"))
    assert rep.spans["repro.run"][0] >= inner


def test_study_spans_feed_the_chunk_records():
    """Two fingerprints (two protocols) of two points each: one chunk
    per fingerprint, each with one dispatch and one drain span, and one
    metrics span per point; the chunk records' dispatch and drain walls
    are exactly the spans' totals."""
    st = Study(Spec(n_cores=16, n_addrs=2, costs={"cycles": 300})).grid(
        protocol=["colibri", "lrsc"], lat=[3, 5])
    with obs.collect() as rep:
        results = st.run()
    assert len(results) == 4 and all(r.ok for r in results)
    assert rep.n_chunks == 2
    dispatch, drain, metrics = (rep.spans[f"repro.sweep.{k}"]
                                for k in ("dispatch", "drain", "metrics"))
    assert dispatch[1] == drain[1] == 2 and metrics[1] == 4
    assert sum(c.compile_s for c in rep.chunks) == dispatch[0]
    assert sum(c.execute_s for c in rep.chunks) == drain[0]
    assert "repro.sweep.isolate" not in rep.spans


def test_dispatch_spans_and_chunks_name_the_topology_lookup(monkeypatch):
    """Every run and chunk says which topology lookup it traced: the
    dispatch spans carry ``topo=`` and each chunk record keeps it."""
    from repro.obs import runreport
    seen = []

    def annotate(name, **args):
        if name.endswith(".dispatch"):
            seen.append((name, args["topo"]))
        return contextlib.nullcontext()

    monkeypatch.setattr(runreport, "TraceAnnotation", annotate)
    base = Spec(protocol="colibri", n_cores=16, costs={"cycles": 200})
    wide = 2 * sim._TOPO_SELECT_BANKS
    points = [base.replace(n_addrs=4),
              base.replace(topology="cluster2", n_addrs=4),
              base.replace(topology="cluster2", n_addrs=wide)]
    with obs.collect() as rep:
        assert run(points[1]).ok
        assert all(r.ok for r in Study.from_specs(points).run())
    forms = ["none", "select", "gather"]
    assert seen == ([("repro.run.dispatch", "select")]
                    + [("repro.sweep.dispatch", f) for f in forms])
    assert [c.topo for c in rep.chunks] == forms
    assert [c["topo"] for c in rep.to_dict()["chunks"]] == forms


def test_dispatch_spans_and_chunks_name_the_kernel_grid(monkeypatch):
    """Every run and chunk says which bank regime it traced: the dispatch
    spans carry ``banks=``, ``bank_tiles=`` (the engine-step kernel's
    grid, 0 on the XLA scan path) and ``to_cores=`` (the bank-to-core
    delivery, ``gather`` with few cores per bank), and each chunk
    record keeps all three."""
    from repro.obs import runreport
    seen = []

    def annotate(name, **args):
        if name.endswith(".dispatch"):
            seen.append((name, args["banks"], args["bank_tiles"],
                         args["to_cores"]))
        return contextlib.nullcontext()

    monkeypatch.setattr(runreport, "TraceAnnotation", annotate)
    base = Spec(protocol="colibri", n_cores=16, costs={"cycles": 100})
    xla = [base.replace(n_addrs=4, backend="xla_cpu"),
           base.replace(n_addrs=512, backend="xla_cpu")]
    tiled = base.replace(n_addrs=512, backend="pallas_interpret")
    with obs.collect() as rep:
        assert run(xla[1]).ok and run(tiled).ok
        assert all(r.ok for r in Study.from_specs(xla + [tiled]).run())
    regimes = [(4, 0, "scatter"), (512, 0, "gather"), (512, 2, "gather")]
    assert seen == ([("repro.run.dispatch", 512, 0, "gather"),
                     ("repro.run.dispatch", 512, 2, "gather")]
                    + [("repro.sweep.dispatch",) + r for r in regimes])
    assert [(c.banks, c.bank_tiles, c.to_cores)
            for c in rep.chunks] == regimes
    assert [(c["banks"], c["bank_tiles"], c["to_cores"])
            for c in rep.to_dict()["chunks"]] == regimes


def test_failed_chunk_records_an_isolate_span(monkeypatch):
    orig = sweep_mod._sweep_group

    def poisoned(rep, dyn, batch):
        if (np.asarray(dyn["seed"]) == 1).any():
            raise RuntimeError("injected chunk failure")
        return orig(rep, dyn, batch)

    poisoned._cache_size = orig._cache_size      # read while collecting
    monkeypatch.setattr(sweep_mod, "_sweep_group", poisoned)
    base = Spec(protocol="lrscwait", n_cores=16, n_addrs=2,
                costs={"cycles": 300})
    with obs.collect() as rep:
        got = list(Study.from_specs(
            [base.replace(seed=s) for s in range(4)]).stream())
    assert sorted(r.ok for r in got) == [False, True, True, True]
    assert rep.spans["repro.sweep.isolate"][1] == 1
    # a chunk that failed at dispatch has no record and no drain
    assert "repro.sweep.drain" not in rep.spans
