"""Distributed-correctness tests. These need >1 device, so they run in a
subprocess with forced host devices (the main pytest process keeps the
default single-device config, per the dry-run isolation rule)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """One train step on a (2,4) mesh == single-device step (same math)."""
    run_sub(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.distributed.sharding import Policy, make_policy, param_specs, shardings_of
from repro.launch.mesh import make_mesh
from repro.launch.train import make_train_step, batch_shardings
from repro.models import build, make_batch
from repro import optim

cfg = get_config("qwen2-7b-smoke")
shape = ShapeSpec("t", 64, 4, "train")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
opt_cfg = optim.AdamWConfig(lr=1e-3)
opt = optim.init(opt_cfg, params)
batch = make_batch(cfg, shape, jax.random.PRNGKey(1))

# single device
step1 = jax.jit(make_train_step(model, opt_cfg, Policy()))
p1, o1, m1 = step1(params, opt, batch)

# sharded
mesh = make_mesh((2, 4), ("data", "model"))
policy = make_policy(mesh, cfg)
stepN = jax.jit(make_train_step(model, opt_cfg, policy),
                in_shardings=(shardings_of(param_specs(params, policy), mesh),
                              None, batch_shardings(batch, policy)))
with jax.set_mesh(mesh):
    pN, oN, mN = stepN(params, opt, batch)

np.testing.assert_allclose(float(m1["loss"]), float(mN["loss"]), rtol=1e-5)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a, np.float32), np.asarray(b, np.float32),
    rtol=5e-3, atol=5e-3), p1, pN)
print("OK sharded == single-device")
""")


def test_moe_ep_sharded_matches_local():
    """EP-sharded deepseek MoE step == local path (generous capacity)."""
    run_sub(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.distributed.sharding import Policy, make_policy, param_specs, shardings_of
from repro.launch.mesh import make_mesh
from repro.models import build, make_batch

cfg = get_config("deepseek-v3-671b-smoke")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
shape = ShapeSpec("t", 32, 4, "train")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = make_batch(cfg, shape, jax.random.PRNGKey(1))

l1, _ = jax.jit(lambda p, b: model.loss(p, b, Policy()))(params, batch)
mesh = make_mesh((2, 2), ("data", "model"))
policy = make_policy(mesh, cfg)
with jax.set_mesh(mesh):
    lN, _ = jax.jit(lambda p, b: model.loss(p, b, policy))(params, batch)
np.testing.assert_allclose(float(l1), float(lN), rtol=2e-4)
print("OK moe ep == local")
""")


def test_production_mesh_shapes():
    run_sub(r"""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.shape == {"data": 16, "model": 16}, m1.shape
m2 = make_production_mesh(multi_pod=True)
assert m2.shape == {"pod": 2, "data": 16, "model": 16}, m2.shape
print("OK meshes")
""", devices=512)


@pytest.mark.slow
def test_dryrun_single_cell_end_to_end(tmp_path):
    """The dry-run entry point works end-to-end for one small cell."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--report-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    rep = json.load(open(os.path.join(
        str(tmp_path), "smollm-135m__decode_32k__pod16x16.json")))
    assert rep["status"] == "ok"
    assert rep["memory"]["peak_bytes"] < 16 * 2**30     # fits HBM
