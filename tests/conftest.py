"""Shared fixtures of the tier-1 suite.

The benchmark's plain reference (``bench/reference.py``) decides
``correct`` on the chip; tier 1 holds the simulator to it on the CPU.
The ``bench/`` modules are loaded from their files, each once per
process under its own name, and ``bench/`` goes on ``sys.path`` because
``cells.reference_point`` imports ``reference`` by name.
"""
import importlib.util
import os
import sys

import pytest

from repro.sync import Spec, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_bench_module(name):
    """``bench/<name>.py`` as the module ``name``, loaded once."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="session")
def bench_module():
    return load_bench_module


@pytest.fixture(scope="session")
def assert_matches_reference():
    """``assert_matches_reference(config, fields)``: run the point
    ``config["spec"]`` plus ``fields`` through ``repro.sync.run`` and
    hold it to the plain reference as the benchmark holds a single run:
    every statistic equal, ``Result.ok`` and ``Result.check()``.
    ``config`` names a file of ``bench/configs/``."""
    cells, check, reference = (load_bench_module(n) for n in
                               ("cells", "check", "reference"))

    def matches(config, fields):
        conf = cells.load_json(os.path.join(BENCH, "configs",
                                            config + ".json"))
        f = {**conf["spec"], **fields}
        got = run(Spec(**f))
        raw = reference.run_points([cells.reference_point(f, True)])[0]
        ref = reference.derive(raw, f["cycles"], conf["energy_model"])
        verdict = check.judge([[got]], [ref])
        assert verdict["numbers"]["fields_off"] == 0, verdict["notes"]
        assert verdict["correct"], verdict

    return matches
