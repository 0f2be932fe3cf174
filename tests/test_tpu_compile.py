"""Ahead-of-time compile guard: the engine's ``pallas_tpu`` path must
lower for a TPU v5e.

The TPU compiler is installed on CPU-only hosts too, and compiles for a
chip that is described rather than attached.  These tests compile the
engine (``sim.simulate``) and the vmapped sweep runner with the fused
``engine_step`` kernel for every registered protocol, at the paper's
256-core platform and at the shapes that once broke lowering: a
multi-tile bank grid (1024 banks), the 1024-core ``cluster2`` machine,
that machine at its full 4096 banks, 4096 cores, and a sweep batch
sharded over four chips.  Each compiled
program must contain the kernel (``tpu_custom_call``).  Nothing runs, so
these say nothing about results or speed: ``chip_smoke.py`` checks those
on the chip.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols, sim, sweep

CYCLES = 64
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_params(monkeypatch, topo, no_persistent_cache):
    """``SimParams`` factory for ``pallas_tpu`` on this TPU-less host:
    the backend check asks which devices are visible, so the test says
    the TPU backends exist."""
    monkeypatch.setattr(sim, "available_backends",
                        lambda: tuple(sim.BACKENDS))

    def make(**kw):
        return sim.SimParams(backend="pallas_tpu", cycles=CYCLES, **kw)
    return make


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_run(p, sharding):
    dyn = {"seed": jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)}
    return jax.jit(lambda d: sim.simulate(p, dyn=d)).lower(dyn).compile()


def _sweep_dyn(batch, sharding):
    return {f: jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharding)
            for f in sim.DYN_FIELDS if f != "n_workers"}


@pytest.mark.parametrize("protocol", protocols.names())
def test_engine_lowers_every_protocol(protocol, tpu_params, topo):
    from jax.sharding import SingleDeviceSharding
    p = tpu_params(protocol=protocol, n_cores=256, n_addrs=1)
    _assert_kernel(_compile_run(p, SingleDeviceSharding(topo.devices[0])))


@pytest.mark.parametrize("shape", [
    dict(protocol="lrsc", n_cores=256, n_addrs=1024),     # 4 bank tiles
    dict(protocol="colibri_hier", n_cores=1024, n_addrs=4,
         topology="cluster2", clusters=4),
    dict(protocol="colibri", n_cores=4096, n_addrs=4),    # 4 core chunks
    # the terapool1024_4096banks cell: 16 bank tiles, gather lookup
    dict(protocol="colibri_hier", n_cores=1024, n_addrs=4096,
         topology="cluster2", clusters=4, workload="zipf_histogram",
         zipf_skew=100),
], ids=["multi_tile", "cluster2_1024", "colibri_4096", "terapool_4096banks"])
def test_engine_lowers_at_scale(shape, tpu_params, topo):
    from jax.sharding import SingleDeviceSharding
    _assert_kernel(_compile_run(tpu_params(**shape),
                                SingleDeviceSharding(topo.devices[0])))


def test_sweep_lowers_vmapped(tpu_params, topo):
    """The Study path: the kernel under ``vmap`` over a sweep batch."""
    from jax.sharding import SingleDeviceSharding
    p = tpu_params(protocol="lrscwait", workload="ms_queue", n_cores=256,
                   n_addrs=2)
    c = sweep._sweep_group.lower(
        p, _sweep_dyn(2, SingleDeviceSharding(topo.devices[0])), 2).compile()
    _assert_kernel(c)


def test_sweep_lowers_sharded_over_four_chips(tpu_params, topo):
    """A sweep batch sharded over 4 chips compiles with no gather of the
    batch: each chip runs the kernel on its own slice (the compiler
    cannot partition a Mosaic kernel itself)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(topo.devices), ("batch",))
    p = dataclasses.replace(
        tpu_params(protocol="colibri", n_cores=256, n_addrs=16),
        n_workers=0)
    c = sweep._sweep_group.lower(
        p, _sweep_dyn(8, NamedSharding(mesh, PartitionSpec("batch"))), 8,
        mesh).compile()
    _assert_kernel(c)
    assert "all-gather" not in c.as_text()
    out = jax.tree.leaves(c.output_shardings)[0]
    assert len(out.device_set) == 4


def _stages():
    """``bench/stages.py``: maps a compiled program's ops to the engine's
    stages, the way a profile of the chip is read."""
    spec = importlib.util.spec_from_file_location(
        "bench_stages", os.path.join(ROOT, "bench", "stages.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_loop_op_resolves_to_a_stage(tpu_params, topo):
    """The 1024-core ``cluster2`` machine of the benchmark's rmw_hot4
    cell: every fusion, reduce-window and custom call of the scan body
    maps to a ``sim.*`` stage.  The rotating priority's cumsum lowers to
    reduce-windows with a bare op name, found through the dataflow; the
    kernel is ``sim.arbitrate`` and named ``engine_step``."""
    from jax.sharding import SingleDeviceSharding
    st = _stages()
    p = tpu_params(protocol="colibri_hier", n_cores=1024, n_addrs=4,
                   topology="cluster2", clusters=4)
    text = _compile_run(p, SingleDeviceSharding(topo.devices[0])).as_text()
    smap = st.stage_map(text)
    heavy = [i for i in st.loop_body(text)
             if i.opcode in ("fusion", "reduce-window", "custom-call")]
    assert len(heavy) > 20
    assert {i.name: smap[i.name] for i in heavy
            if not smap[i.name].startswith("sim.")} == {}
    windows = [i for i in heavy if i.opcode == "reduce-window"]
    assert windows and all(i.scope is None for i in windows)
    assert {smap[i.name] for i in windows} == {"sim.network"}
    kernels = [i for i in heavy if "tpu_custom_call" in i.text]
    assert len(kernels) == 1
    assert smap[kernels[0].name] == "sim.arbitrate"
    assert kernels[0].name.startswith("engine_step")
