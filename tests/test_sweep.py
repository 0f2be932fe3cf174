"""The vmapped sweep runner must be a pure batching transform: results
identical to per-config ``sim.run``, regardless of how configs are
grouped, padded (mixed ``n_addrs`` share one bank allocation), or
ordered."""
import dataclasses

import numpy as np
import pytest

from repro.core.sim import SimParams, run
from repro.core.sweep import STATIC_FIELDS, sweep, sweep_grid

EXACT_KEYS = ("ops", "msgs", "polls", "sleep_cyc", "backoff_cyc",
              "bank_ops", "net_stall", "throughput", "fairness_min",
              "fairness_max",
              # the metrics layer derives these from the same integer
              # state, so sweep and run agree exactly, not approximately
              "lat_hist", "lat_max", "lat_p50", "lat_p95",
              "jain_fairness", "fairness_span", "energy_pj_per_op")


def _assert_same(swept, ref):
    for k in EXACT_KEYS:
        assert np.array_equal(np.asarray(swept[k]), np.asarray(ref[k])), k


def test_sweep_matches_run_mixed_axes():
    """Mixed contention/latency/seed configs, two protocols: every point
    equals its sequential run() twin exactly (integer engine state)."""
    configs = [
        SimParams(protocol="colibri", n_cores=32, cycles=1200, n_addrs=1),
        SimParams(protocol="colibri", n_cores=32, cycles=1200, n_addrs=8,
                  lat=3, seed=1),
        SimParams(protocol="lrsc", n_cores=32, cycles=1200, n_addrs=4,
                  work=6),
        SimParams(protocol="lrsc", n_cores=32, cycles=1200, n_addrs=1,
                  backoff=128, backoff_exp=1),
    ]
    for cfg, swept in zip(configs, sweep(configs)):
        _assert_same(swept, run(cfg))


def test_sweep_matches_run_queue_and_workers():
    """Queue-based protocol with traced n_workers + head-of-line blocking
    (the Fig.5 regime) through the sweep path."""
    configs = [
        SimParams(protocol="lrscwait", n_cores=32, cycles=1200, n_addrs=1,
                  n_workers=w, net_bw=13, hol_block=16) for w in (0, 4, 8)
    ]
    for cfg, swept in zip(configs, sweep(configs)):
        ref = run(cfg)
        _assert_same(swept, ref)
        if cfg.n_workers:
            assert swept["worker_rate"] == ref["worker_rate"]


def test_sweep_grid_product_order():
    res = sweep_grid(SimParams(protocol="amo", n_cores=16, cycles=600),
                     n_addrs=(1, 4), seed=(0, 1))
    assert len(res) == 4
    assert [(r["_config"].n_addrs, r["_config"].seed) for r in res] == \
        [(1, 0), (1, 1), (4, 0), (4, 1)]
    for r in res:
        _assert_same(r, run(r["_config"]))


def test_sweep_rejects_non_sweepable_axis():
    with pytest.raises(ValueError):
        sweep_grid(SimParams(), n_cores=(8, 16))


def test_sweep_rejects_bad_max_batch():
    with pytest.raises(ValueError):
        sweep([SimParams(n_cores=8, cycles=100)], max_batch=0)


def test_sweep_mixed_worker_axis_chunks_identical():
    """A fingerprint group mixing worker and worker-free configs stays
    bit-identical to run() even when chunking isolates a worker-free
    chunk: the dropped n_workers axis must not fall back to the group
    leader's nonzero static value (phantom Fig.5 workers)."""
    configs = [
        SimParams(protocol="colibri", n_cores=16, n_addrs=1, cycles=500,
                  n_workers=w) for w in (8, 0, 4)
    ]
    for mb in (None, 1):
        for cfg, swept in zip(configs, sweep(configs, max_batch=mb)):
            ref = run(cfg)
            _assert_same(swept, ref)
            assert np.array_equal(np.asarray(swept["w_served"]),
                                  np.asarray(ref["w_served"]))


def test_sweep_chunking_identical():
    """max_batch chunking is invisible: a 5-point group split into 2-point
    chunks (and into singletons) returns exactly the unchunked results."""
    configs = [SimParams(protocol="colibri", n_cores=32, cycles=900,
                         n_addrs=a, seed=s)
               for a, s in [(1, 0), (8, 1), (4, 2), (1, 3), (16, 4)]]
    ref = [run(c) for c in configs]
    for mb in (2, 1):
        for want, swept in zip(ref, sweep(configs, max_batch=mb)):
            _assert_same(swept, want)


def test_sweep_one_transfer_per_chunk(monkeypatch):
    """A 100-point single-fingerprint grid moves device->host in ONE
    ``jax.device_get`` of the whole result pytree (the former per-key
    ``np.asarray`` loop paid one host sync per array per group); with
    max_batch=30 it is one transfer per chunk.  This is the mechanism
    behind the batched-transfer timing win, asserted deterministically
    instead of with a flaky wall-clock bound."""
    import jax

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    base = SimParams(protocol="amo", n_cores=16, cycles=300)
    # n_addrs 9..16 share one power-of-two bank bucket -> one group
    res = sweep_grid(base, n_addrs=(9, 12, 14, 16),
                     seed=tuple(range(25)))                  # 100 points
    assert len(res) == 100
    assert len(calls) == 1                                   # one chunk
    calls.clear()
    res2 = sweep_grid(base, max_batch=30, n_addrs=(9, 12, 14, 16),
                      seed=tuple(range(25)))
    assert len(calls) == 4                                   # ceil(100/30)
    for a, b in zip(res, res2):
        _assert_same(a, b)


def test_sweep_shards_across_devices():
    """With >1 device visible the chunk batch axis is sharded across the
    mesh; results stay bit-identical to per-config run().  Forced host
    devices require a fresh process (XLA_FLAGS is read at jax init)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=2"),
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    code = (
        "import jax, numpy as np\n"
        "assert jax.device_count() == 2, jax.device_count()\n"
        "from repro.core.sim import SimParams, run\n"
        "from repro.core.sweep import sweep\n"
        "cfgs = [SimParams(protocol='colibri', n_cores=16, cycles=300,\n"
        "                  n_addrs=a, seed=s)\n"
        "        for a, s in [(1, 0), (4, 1), (2, 2)]]\n"   # odd: pads
        "for c, r in zip(cfgs, sweep(cfgs)):\n"
        "    q = run(c)\n"
        "    assert np.array_equal(r['ops'], q['ops'])\n"
        "    assert int(r['msgs']) == int(q['msgs'])\n"
        "    assert int(r['polls']) == int(q['polls'])\n"
        "print('sharded-ok')\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "sharded-ok" in out.stdout


def test_static_fields_cover_simparams():
    """Every SimParams field is either a static grouping key or a sweep
    axis — adding a field without classifying it should fail loudly."""
    from repro.core.sim import DYN_FIELDS
    fields = {f.name for f in dataclasses.fields(SimParams)}
    assert fields == set(STATIC_FIELDS) | set(DYN_FIELDS)


@pytest.mark.parametrize("from_env", [True, False])
def test_persistent_cache_placement(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` places the compile cache from
    outside, and compiles land there; unset, the cache sits at the fixed
    ``<checkout>/.jax_cache``.  A fresh process, so the cache setting
    never leaks into this test session."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.sync import enable_persistent_cache\n"
        "path = enable_persistent_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == path\n"
        + ("jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
           if from_env else "")
        + "print(path)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    path = out.stdout.strip().splitlines()[-1]
    if from_env:
        assert path == str(tmp_path)
        assert any(tmp_path.iterdir())           # the compile landed here
    else:
        assert path == os.path.join(repo, ".jax_cache")
