"""A run with the timed path broken underneath must come out not
correct.  Each case drives the harness on the CPU (the look for a chip
steered from the test) at a tiny size, with one fault planted in the
simulator:

* ``frozen``: the engine's scan returns its state unchanged;
* ``half_batch``: a Study chunk computes the first half of its batch
  and repeats it in place of the rest;
* ``no_exchange``: on four devices, every device's share of a sharded
  chunk is replaced by device 0's (the gather between chips left out);
* ``altered``: one statistic is altered where the engine produces it.

``no_exchange`` needs four devices, so it runs in a child process with
four virtual CPU devices: ``python bench/tests/test_faults.py sharded``.
"""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _plant(fault, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core import sim, sweep
    jax.clear_caches()
    if fault == "frozen":
        monkeypatch.setattr(sim, "lax", types.SimpleNamespace(
            scan=lambda f, init, xs, unroll=1: (init, None)))
    elif fault == "altered":
        real = sim.simulate

        def altered(*a, **k):
            out = dict(real(*a, **k))
            out["msgs"] = out["msgs"] + 1
            return out
        monkeypatch.setattr(sim, "simulate", altered)
        monkeypatch.setattr(sweep, "simulate", altered)
    elif fault in ("half_batch", "no_exchange"):
        real = sweep._sweep_group

        def broken(rep, dyn, batch, mesh=None):
            out = real(rep, dyn, batch, mesh)
            parts = 2 if fault == "half_batch" else len(jax.devices())
            k = max(batch // parts, 1)
            return jax.tree.map(
                lambda x: jnp.concatenate([x[:k]] * parts)[:x.shape[0]]
                if x.ndim else x, out)
        broken._cache_size = real._cache_size
        monkeypatch.setattr(sweep, "_sweep_group", broken)
    else:
        raise ValueError(fault)


def _drive(name, fault, monkeypatch):
    import run
    from test_rehearsal import no_chip_check, tiny
    _plant(fault, monkeypatch)
    try:
        return run.run_cell(tiny(name), 7, 0.2, False, require=no_chip_check)
    finally:
        monkeypatch.undo()
        import jax
        jax.clear_caches()


@pytest.mark.parametrize("name,fault", [
    ("terapool1024.rmw_hot4", "frozen"),
    ("terapool1024.rmw_hot4", "altered"),
    ("mempool256.grid50", "frozen"),
    ("mempool256.grid50", "half_batch"),
    ("mempool256.grid50", "altered"),
])
def test_fault_is_not_correct(name, fault, monkeypatch):
    out = _drive(name, fault, monkeypatch)
    assert out["correct"] is False
    assert out["failed"] > 0
    assert out["checks"]["fields_off"]["value"] > 0


def test_sharded_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, "sharded"], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, broken = (json.loads(x) for x in p.stdout.splitlines()
                     if x.startswith("{"))
    assert sound["correct"] is True and sound["device"]["count"] == 4
    assert broken["correct"] is False
    assert broken["checks"]["fields_off"]["value"] > 0


if __name__ == "__main__" and sys.argv[1:] == ["sharded"]:
    import conftest  # noqa: F401 - paths and the test compile cache
    import run
    from test_rehearsal import no_chip_check, tiny
    c = tiny("mempool256.grid50")
    c["chips"] = 4
    print(json.dumps(run.run_cell(c, 11, 0.2, False,
                                  require=no_chip_check)))
    mp = pytest.MonkeyPatch()
    _plant("no_exchange", mp)
    print(json.dumps(run.run_cell(c, 11, 0.2, False,
                                  require=no_chip_check)))
