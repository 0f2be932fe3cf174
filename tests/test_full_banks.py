"""TeraPool's full-L1 regime at a small size: many banks under Zipf skew.

The benchmark's ``terapool1024_4096banks`` configuration runs 1024 cores
over 4096 banks, one histogram bin per bank, with Zipf keys (s = 1.0) on
the ``cluster2`` machine under ``colibri_hier``.  Past
``sim._TOPO_SELECT_BANKS`` banks the topology lookup takes its gather
form, and past one bank tile the engine-step kernel runs a grid.  Here
the same regime at 64 cores over 512 banks (the gather form, 2 kernel
tiles): both backends agree bit for bit, and both agree with the
benchmark's plain reference (``bench/reference.py``, loaded from its
file) on every statistic, while the reference with first-come bank
service broken (the cell's ``fifo`` control) does not.
"""
import json
import os

import pytest

from repro.core import sim
from repro.kernels.engine_step.ops import PREF_BLOCK_A
from repro.sync import Spec, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CONFIG = os.path.join(BENCH, "configs", "terapool1024_4096banks.json")
N_CORES, BANKS, CYCLES, SEED = 64, 512, 400, 2**31 - 12345


@pytest.fixture(scope="module")
def check(bench_module):
    return bench_module("check")


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fields(config):
    """The configuration's Spec fields, cut to the small size."""
    return {**config["spec"], "workload": "zipf_histogram",
            "n_cores": N_CORES, "n_addrs": BANKS, "cycles": CYCLES,
            "seed": SEED}


@pytest.fixture(scope="module")
def results(fields):
    return {bk: run(Spec(**fields, backend=bk)).stats
            for bk in ("xla_cpu", "pallas_interpret")}


@pytest.fixture(scope="module")
def reference(config, fields, bench_module):
    """The plain reference's statistics, and its ``fifo`` control's."""
    ref = bench_module("reference")
    point = {k: fields[k] for k in (
        "protocol", "workload", "topology", "clusters", "n_groups",
        "n_cores", "cycles", "q_slots") + ref.DYN}
    point.update(banks=ref.bank_count(BANKS),
                 static=("n_addrs", "zipf_skew"))
    energy = config["energy_model"]
    return {broken: ref.derive(ref.run_points([point], broken=broken)[0],
                               CYCLES, energy)
            for broken in (None, "fifo")}


def test_the_regime_is_the_full_bank_one(fields):
    p = Spec(**fields, backend="pallas_interpret").to_params()
    assert p.n_addrs == BANKS > sim._TOPO_SELECT_BANKS
    assert sim.dispatch_args(p) == dict(
        topo="gather", banks=BANKS, bank_tiles=BANKS // PREF_BLOCK_A,
        to_cores="gather")
    assert BANKS // PREF_BLOCK_A == 2


def test_backends_are_bit_identical(results, check):
    assert check.fields_differ(results["xla_cpu"],
                               results["pallas_interpret"]) == []


@pytest.mark.parametrize("backend", ["xla_cpu", "pallas_interpret"])
def test_backend_equals_the_reference(backend, results, reference, check):
    got = results[backend]
    assert check.fields_differ(got, reference[None]) == []
    # the regime is the contended one: many banks busy, cores asleep in
    # their cluster's queues, and the bins' totals are the completions
    assert (got["addr_ops"] > 0).sum() > 16
    assert got["sleep_cyc"] > 0
    assert got["addr_ops"].sum() == got["ops"].sum() > 0


def test_fifo_control_disagrees(results, reference, check):
    assert check.fields_differ(results["xla_cpu"], reference["fifo"])
