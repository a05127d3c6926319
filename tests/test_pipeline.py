"""Pipeline parallelism: GPipe schedule == sequential model, exactly."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_pp_loss_and_grads_match_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import smoke_of
from repro.models import bundle_for
from repro.models.sharding import set_rules
from repro.runtime.pipeline import make_pp_loss_fn, make_pp_mesh

set_rules({})
cfg = smoke_of("qwen3-1.7b")           # 2 layers
cfg = dataclasses.replace(cfg, n_layers=4, dtype="float32")
bundle = bundle_for(cfg)
key = jax.random.PRNGKey(0)
params = bundle.init(cfg, key)
B, S = 8, 16
batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
         "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}

ref_loss, ref_grads = jax.value_and_grad(
    lambda p: bundle.loss_fn(cfg, p, batch))(params)

mesh = make_pp_mesh(4)
pp_loss_fn = make_pp_loss_fn(cfg, mesh, n_stages=4, n_micro=4)
with jax.set_mesh(mesh):
    pp_loss, pp_grads = jax.jit(jax.value_and_grad(pp_loss_fn))(params, batch)

err = abs(float(ref_loss) - float(pp_loss))
assert err < 1e-4, (float(ref_loss), float(pp_loss))
for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(pp_grads)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                               rtol=2e-2, err_msg=str(ka))
print("PP_OK", float(ref_loss), float(pp_loss))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PP_OK" in out.stdout
