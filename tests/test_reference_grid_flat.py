"""The simulator against the benchmark's plain reference on ``flat``.

Every protocol and workload ``bench/reference.py`` supports, on the flat
crossbar, at 16 cores over 8 addresses (each core reads its own bank's
outcome: the gather form of ``sim.bank_to_core``), and the histogram
workloads again at 2 addresses (each bank writes its cores: the scatter
form).  Each point runs through ``repro.sync.run`` and must agree with
the reference in every statistic, with ``Result.check()`` passing, as
the benchmark's ``correct`` demands on the chip.
``tests/test_reference_grid_cluster.py`` runs the same grid on
``cluster2``.
"""
import pytest

from repro.core import sim
from repro.sync import Spec

PROTOCOLS = ("colibri", "lrscwait", "mwait_lock", "lrsc", "amo_lock",
             "colibri_hier")
WORKLOADS = ("rmw_loop", "ms_queue", "treiber_stack", "zipf_histogram",
             "barrier_phases")
POINT = dict(n_cores=16, cycles=400, zipf_skew=100, n_groups=4, seed=11)


def _assert_matches(assert_matches_reference, fields, to_cores):
    assert sim.bank_to_core(Spec(**fields).to_params()) == to_cores
    assert_matches_reference("mempool256", fields)


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_gather_form_matches_reference(protocol, workload,
                                       assert_matches_reference):
    _assert_matches(assert_matches_reference,
                    dict(POINT, protocol=protocol, workload=workload,
                         topology="flat", n_addrs=8), "gather")


@pytest.mark.parametrize("workload", ("rmw_loop", "zipf_histogram"))
@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_scatter_form_matches_reference(protocol, workload,
                                        assert_matches_reference):
    _assert_matches(assert_matches_reference,
                    dict(POINT, protocol=protocol, workload=workload,
                         topology="flat", n_addrs=2), "scatter")
