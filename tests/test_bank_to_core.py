"""The two directions in which per-bank results reach the core lanes.

A bank's result of a cycle (its arbitration outcome, a queue wake-up)
belongs to one core, and that core targets the bank.  ``sim.bank_to_core``
picks, from the static shape, whether each bank writes its core
(``"scatter"``, one index per bank) or each core reads its own bank
(``"gather"``, one index per core, taken below
``sim._SCATTER_CORES_PER_BANK`` cores per bank).
Forcing either form gives the same bits: every statistic is identical,
on both backends, for the queue protocols whose wake-ups take the form
and for ``ticket_lock``, whose kernel writes a per-core ticket; above
and below the crossover; and under holder kills with the watchdog on,
where a woken or served core could stop targeting its bank if the
engine's invariant broke.  In the gather form the compiled ``sim.wake``
and ``sim.arbitrate`` stages index nothing by bank.
"""
import importlib.util
import os
import re

import jax
import pytest

from repro.core import sim
from repro.sync import Spec, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 - 4242


def _bench_module(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(ROOT, "bench", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check = _bench_module("check")

PROTOCOLS = ["colibri_hier", "colibri", "lrscwait", "mwait_lock", "nb_feb",
             "ticket_lock"]
#: (cores, banks): TeraPool's four banks per core, cut down, and a few
#: hot banks
SHAPES = {"banks_gt_cores": (64, 512), "banks_le_cores": (64, 16)}
BACKENDS = ["xla_cpu", "pallas_interpret"]


def _force(monkeypatch, form):
    """Trace every later run in ``form``.  The engine is jitted anew over
    a function of its own: jit caches its traces by function and static
    arguments, so equal ``SimParams`` would reuse the other form's."""
    monkeypatch.setattr(sim, "bank_to_core", lambda p: form)
    monkeypatch.setattr(sim, "_run", jax.jit(lambda p: sim.simulate(p),
                                             static_argnums=0))


def _both_forms(monkeypatch, **fields):
    got = {}
    for form in ("scatter", "gather"):
        _force(monkeypatch, form)
        got[form] = run(Spec(**fields)).stats
    return got


K = sim._SCATTER_CORES_PER_BANK
#: (cores, banks, form): each side of the crossover, and the benchmark's
#: shapes (rmw_hot4, grid50's widest point, zipf_hist)
RULE = [(64, 64 // K, "scatter"), (64, 64 // K + 1, "gather"),
        (1024, 1024 // K, "scatter"), (1024, 1024 // K + 1, "gather"),
        (1024, 4, "scatter"), (256, 64, "scatter"), (1024, 4096, "gather")]


@pytest.mark.parametrize("n,a,form", RULE)
def test_the_rule_compares_banks_with_cores(n, a, form):
    p = sim.SimParams(protocol="colibri_hier", n_cores=n, n_addrs=a)
    assert sim.bank_to_core(p) == form
    assert sim.dispatch_args(p)["to_cores"] == form


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_forms_agree_bit_for_bit(monkeypatch, protocol, shape, backend):
    n, a = SHAPES[shape]
    got = _both_forms(monkeypatch, protocol=protocol,
                      workload="zipf_histogram", topology="cluster2",
                      clusters=4, n_cores=n, n_addrs=a, backend=backend,
                      seed=SEED, costs={"cycles": 400})
    assert check.fields_differ(got["scatter"], got["gather"]) == []
    assert got["gather"]["ops"].sum() > 0
    if protocol != "ticket_lock":       # cores sleep in the bank queues
        assert got["gather"]["sleep_cyc"] > 0


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("protocol", ["colibri_hier", "lrscwait"])
def test_forms_agree_under_holder_kills(monkeypatch, protocol, backend):
    n, a = SHAPES["banks_gt_cores"]
    got = _both_forms(monkeypatch, protocol=protocol,
                      workload="zipf_histogram", n_cores=n, n_addrs=a,
                      backend=backend, seed=SEED, costs={"cycles": 600},
                      n_kill=4, kill_cyc=100, kill_holder=1, watchdog_cyc=32)
    assert check.fields_differ(got["scatter"], got["gather"]) == []
    assert got["gather"]["faults_injected"] == 4
    assert got["gather"]["recoveries"] > 0


def _bank_indexed(p, stages=("sim.wake", "sim.arbitrate")):
    """Gathers and scatters of ``stages`` in ``p``'s compiled program that
    take one index per bank."""
    txt = jax.jit(lambda: sim.simulate(p)).lower().compile().as_text()
    shapes = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", txt))
    found = []
    for line in txt.splitlines():
        m = re.search(r"= \S+ (gather|scatter)\(%\S+?, %(\S+?)[,)]", line)
        scope = re.search(r'op_name="[^"]*/(sim\.\w+)/[^/"]*"', line)
        if m and scope and scope.group(1) in stages:
            count = int(shapes[m.group(2)].split(",")[0])
            if count == p.n_addrs:
                found.append((scope.group(1), m.group(1)))
    return found


@pytest.mark.parametrize("protocol", ["colibri_hier", "lrscwait"])
def test_gather_form_indexes_no_bank(monkeypatch, protocol):
    n, a = SHAPES["banks_gt_cores"]
    p = sim.SimParams(protocol=protocol, workload="zipf_histogram",
                      topology="cluster2", n_cores=n, n_addrs=a, cycles=20,
                      backend="pallas_interpret")
    assert sim.bank_to_core(p) == "gather"
    assert _bank_indexed(p) == []
    _force(monkeypatch, "scatter")
    stages = {s for s, _ in _bank_indexed(p)}
    assert stages == {"sim.wake", "sim.arbitrate"}
