"""Rehearsal of the harness on the CPU: every cell of BENCHMARK.json
loads and builds its points, and a run of a tiny single cell and a tiny
Study cell, defined here, goes through set-up, window, checks and the
result line with the look for a chip steered from the test."""
import copy
import json
import os
import subprocess
import sys
import types

import pytest

import cells
import check
import run
import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAMES = [w["name"] for w in cells.benchmark()["workloads"]]


def no_chip_check(jax, chips):
    return None


def tiny(name, n_cores=16, cycles=300):
    """A cell's data at a size the CPU runs in seconds."""
    c = copy.deepcopy(cells.cell(name))
    c["name"] = "tiny." + name
    c["chips"] = 1
    c["config"]["spec"]["n_cores"] = n_cores
    c["traffic"]["spec"]["cycles"] = cycles
    if c["traffic"]["mode"] == "study":
        ax = c["traffic"]["axes"]
        ax["workload"] = [ax["workload"][0], ax["workload"][3]]
        ax["protocol"] = ax["protocol"][:2]
    elif c["traffic"]["spec"].get("n_addrs", 1) > 16:
        c["traffic"]["spec"]["n_addrs"] = 16
    return c


@pytest.mark.parametrize("name", NAMES)
def test_cell_loads_and_builds_points(name):
    from repro.sync import Spec
    c = cells.cell(name)
    assert c["metrics"]["end_to_end"][0]["name"] == "setup_s"
    assert c["metrics"]["per_layer"]
    fields = cells.point_fields(c, 2**31 + 12345)
    specs = [Spec(**f) for f in fields]
    single = c["traffic"]["mode"] == "single"
    assert len(specs) == (1 if single else 50)
    assert len(set(specs)) == len(specs)
    for f, s in zip(fields, specs):
        pt = cells.reference_point(f, single)
        assert pt["n_cores"] == s.topology.n_cores
        assert pt["banks"] >= s.topology.n_addrs
        assert 0 <= pt["seed"] < 2**31


def test_seed_mapping_is_stable_and_fits_int32():
    c = cells.cell("mempool256.grid50")
    a = cells.point_fields(c, 4_294_967_311)
    assert a == cells.point_fields(c, 4_294_967_311)
    assert a != cells.point_fields(c, 4_294_967_312)
    assert max(f["seed"] for f in a) < 2**31


@pytest.mark.parametrize("trace", [False])
@pytest.mark.parametrize("name", ["terapool1024.rmw_hot4",
                                  "mempool256.grid50"])
def test_tiny_cell_runs_to_a_result_line(name, trace):
    c = tiny(name)
    out = run.run_cell(c, 2**31 + 99, 0.5, trace, require=no_chip_check)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= (1 if "rmw" in name else 8)
    want = {m["name"] for m in c["metrics"]["end_to_end"]}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["checks"] == {k: {"value": 0, "limit": 0}
                             for k in check.LIMITS}
    json.dumps(out)


#: a traced window of 10 ms on one device: 5 ms of XLA operations, and
#: on the kernel path 2 ms of Pallas kernel calls, fewer calls than cycles
XLA_OPS = [(0.000, 0.003, "fusion.1", False), (0.004, 0.006, "fusion.2", False)]
KERNEL_OPS = [(0.006, 0.007, "closed_call.16", True),
              (0.008, 0.009, "closed_call.16", True)]


@pytest.mark.parametrize("path", ["xla_scan", "kernel_steps_k_cycles"])
def test_per_cycle_metrics_count_simulated_cycles(path, monkeypatch):
    # the traced cycles are those of the whole runs traced (one run of
    # 300 cycles here), whatever the device trace holds: without the
    # kernel the scan stages are still read, and a kernel that steps
    # many cycles per call is not read as one cycle per call
    ops = XLA_OPS + (KERNEL_OPS if path != "xla_scan" else [])
    raw = {"devices": {"/device:TPU:0": ops},
           "spans": [(0.0, 0.0, "bench.trace_start"),
                     (0.010, 0.010, "bench.trace_stop")]}
    real_load = run._load
    tr = real_load("trace")
    fake = types.SimpleNamespace(reduce_dir=lambda d: tr.reduce(raw))
    monkeypatch.setattr(run, "_load", lambda rel, *a: (
        fake if rel == "trace" else real_load(rel, *a)))
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.0)
    # the CPU has no peaks: read the roofline against the v5e's
    monkeypatch.setattr(work, "peaks", lambda kind, real=work.peaks: real(
        "TPU v5 lite"))
    out = run.run_cell(tiny("terapool1024.rmw_hot4"), 5, 0.2, True,
                       require=no_chip_check)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"] is True
    assert m["scan_stages_us_per_cycle"] == pytest.approx(1e6 * 0.005 / 300)
    assert m["device_idle_pct.run"] == pytest.approx(
        50.0 if path == "xla_scan" else 30.0)
    if path == "xla_scan":
        assert "engine_step_us_per_cycle" not in m
        assert "engine_step_roofline" not in m
    else:
        assert m["engine_step_us_per_cycle"] == pytest.approx(
            1e6 * 0.002 / 300)
        assert 0 < m["engine_step_roofline"] < 100


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        NAMES[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
