"""TeraPool's full-L1 regime at a small size: many banks under Zipf skew.

The benchmark's two full-L1 configurations run 1024 cores over 4096
banks, one histogram bin per bank, with Zipf keys (s = 1.0) on the
``cluster2`` machine: ``terapool1024_4096banks`` under ``colibri_hier``
(per-cluster queues, bank state stored with banks on the last axis) and
``terapool1024_4096banks_colibri`` under the paper's ``colibri`` (one
queue of n slots per bank, banks-first).  Past
``sim._TOPO_SELECT_BANKS`` banks the topology lookup takes its gather
form, and past one bank tile the engine-step kernel runs a grid.  Here
the same regime at 64 cores over 512 banks (the gather form, 2 kernel
tiles), for each configuration: both backends agree bit for bit, and
both agree with the benchmark's plain reference (``bench/reference.py``,
loaded from its file) on every statistic, while the reference with
first-come bank service broken (the cell's ``fifo`` control) does not.
"""
import json
import os

import pytest

from repro.core import sim
from repro.kernels.engine_step.ops import PREF_BLOCK_A
from repro.sync import Spec, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
N_CORES, BANKS, CYCLES, SEED = 64, 512, 400, 2**31 - 12345
#: configuration -> (queue slots per bank row, banks on the last axis)
CONFIGS = {"terapool1024_4096banks": (N_CORES // 4, 1),
           "terapool1024_4096banks_colibri": (N_CORES, 0)}


@pytest.fixture(scope="module")
def check(bench_module):
    return bench_module("check")


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def config(request):
    with open(os.path.join(BENCH, "configs", request.param + ".json")) as f:
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
    """The plain reference's statistics, and its ``fifo`` control's, on
    the point the harness builds for a single run."""
    ref, cells = bench_module("reference"), bench_module("cells")
    point = cells.reference_point(fields, single=True)
    energy = config["energy_model"]
    return {broken: ref.derive(ref.run_points([point], broken=broken)[0],
                               CYCLES, energy)
            for broken in (None, "fifo")}


def test_the_regime_is_the_full_bank_one(config, fields):
    p = Spec(**fields, backend="pallas_interpret").to_params()
    q_cap, bank_last = CONFIGS[config["name"]]
    assert p.n_addrs == BANKS > sim._TOPO_SELECT_BANKS
    assert sim.dispatch_args(p) == dict(
        topo="gather", banks=BANKS, bank_tiles=BANKS // PREF_BLOCK_A,
        to_cores="gather", q_cap=q_cap, bank_last=bank_last)
    assert BANKS // PREF_BLOCK_A == 2


def test_backends_are_bit_identical(results, check):
    assert check.fields_differ(results["xla_cpu"],
                               results["pallas_interpret"]) == []


@pytest.mark.parametrize("backend", ["xla_cpu", "pallas_interpret"])
def test_backend_equals_the_reference(backend, config, results, reference,
                                      check):
    got = results[backend]
    assert check.fields_differ(got, reference[None]) == []
    # the regime is the contended one: many banks busy, cores asleep in
    # the bank queues, and the bins' totals are the completions
    assert (got["addr_ops"] > 0).sum() > 16
    assert got["sleep_cyc"] > 0
    assert got["addr_ops"].sum() == got["ops"].sum() > 0
    if config["spec"]["protocol"] == "colibri":
        # the distributed queue never fills: no core ever polls
        assert got["polls"] == 0


def test_fifo_control_disagrees(results, reference, check):
    assert check.fields_differ(results["xla_cpu"], reference["fifo"])
