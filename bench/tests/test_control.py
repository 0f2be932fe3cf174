"""The controls at a size a test run holds: the simulator's output
agrees with the plain reference in every statistic, and the reference
with a broken guarantee (banks serve their newest request first, or
latency runs from the bank instead of from the request) does not agree, on
every seed."""
import pytest

import cells
import check
import reference

CASES = {
    # a hot-word single run and a small study of queue protocols
    "single": dict(protocol="colibri", workload="rmw_loop", topology="flat",
                   n_cores=32, cycles=800, n_addrs=2, zipf_skew=0),
    "bins": dict(protocol="colibri", workload="zipf_histogram",
                 topology="flat", n_cores=32, cycles=800, n_addrs=128,
                 zipf_skew=0),
    "hier": dict(protocol="colibri_hier", workload="rmw_loop",
                 topology="cluster2", clusters=4, n_cores=64, cycles=800,
                 n_addrs=4),
    "study": dict(protocol="mwait_lock", workload="ms_queue",
                  topology="flat", n_cores=32, cycles=800, n_addrs=2,
                  modify=8),
}
CONFIG = cells.load_json(f"{cells.BENCH}/configs/mempool256.json")
ENERGY = CONFIG["energy_model"]


@pytest.mark.parametrize("case,broken", [
    ("single", "fifo"), ("hier", "fifo"), ("study", "fifo"),
    ("single", "latency"), ("bins", "latency"), ("study", "latency")])
def test_program_matches_reference_and_control_fails(case, broken):
    from repro.sync import Spec, Study, run
    single = case != "study"
    fields = [{**CONFIG["spec"], **CASES[case], "seed": s} for s in (5, 6, 7)]
    specs = [Spec(**f) for f in fields]
    if single:
        got = [[run(s)] for s in specs]
    else:
        got = [Study.from_specs(specs).run()]
    pts = [cells.reference_point(f, single) for f in fields]
    for control, want_correct in ((None, True), (broken, False)):
        raw = reference.run_points(pts, broken=control)
        ref = [reference.derive(r, 800, ENERGY) for r in raw]
        if single:
            verdicts = [check.judge([g], [r]) for g, r in zip(got, ref)]
        else:
            verdicts = [check.judge(got, ref)]
        for v in verdicts:
            assert v["correct"] is want_correct, v
            assert (v["numbers"]["fields_off"] == 0) is want_correct
