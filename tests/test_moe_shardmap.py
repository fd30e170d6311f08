"""shard_map ragged all-to-all MoE dispatch vs the dense oracle — needs a
real multi-device mesh, so runs in an 8-host-device subprocess."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses
from repro.configs import get_smoke_config
from repro.models import moe as moe_mod
from repro.launch.mesh import make_mesh
from repro.models.moe_shardmap import apply_moe_shardmap

cfg = get_smoke_config("deepseek-moe-16b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, num_experts=8, top_k=2))
mesh = make_mesh((2, 4), ("data", "model"))   # ep=4, 2 experts/device
key = jax.random.PRNGKey(0)
p = moe_mod.init_moe(key, cfg, jnp.float32)
for B, S in ((4, 8), (2, 13)):       # 13: exercises the sequence padding
    x = jax.random.normal(jax.random.PRNGKey(B * 100 + S),
                          (B, S, cfg.d_model)) * 0.5
    yd, _ = moe_mod.apply_moe(p, cfg, x)
    with mesh:
        ys, _ = jax.jit(lambda p, x: apply_moe_shardmap(
            p, cfg, x, mesh, capacity_factor=16.0))(p, x)
    err = float(jnp.max(jnp.abs(yd - ys)))
    assert err < 2e-4, (B, S, err)
    # gradients flow through the all_to_all exchange
    g = jax.grad(lambda p: apply_moe_shardmap(
        p, cfg, x, mesh, capacity_factor=16.0)[0].sum())(p)
    gd = jax.grad(lambda p: moe_mod.apply_moe(p, cfg, x)[0].sum())(p)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gd)):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-4
print("OK")
"""


@pytest.mark.slow
def test_shardmap_dispatch_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
