"""The simulator against the benchmark's plain reference on ``cluster2``.

The grid of ``tests/test_reference_grid_flat.py`` (every protocol and
workload ``bench/reference.py`` supports, 16 cores over 8 addresses) on
the two-level cluster tree with 4 clusters: remote accesses pay the
level's extra latency and share its link budget, and ``colibri_hier``
queues per cluster.  Each point must agree with the reference in every
statistic, hops included, with ``Result.check()`` passing.
"""
import pytest

from test_reference_grid_flat import POINT, PROTOCOLS, WORKLOADS


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_cluster2_matches_reference(protocol, workload,
                                    assert_matches_reference):
    assert_matches_reference(
        "mempool256", dict(POINT, protocol=protocol, workload=workload,
                           topology="cluster2", clusters=4, n_addrs=8))
