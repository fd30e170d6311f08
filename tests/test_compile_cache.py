"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, and otherwise to <checkout>/.jax_cache (fresh processes: the cache
directory is process-wide JAX state)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import json, os
import jax, jax.numpy as jnp
from repro.common.compile_cache import enable_compile_cache
got = enable_compile_cache()
if os.environ.get("COMPILE_ONE"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"returned": got,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(env_updates):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_updates)
    r = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    cache = tmp_path / "cache"
    out = _run({"JAX_COMPILATION_CACHE_DIR": str(cache), "COMPILE_ONE": "1"})
    assert out == {"returned": str(cache), "config": str(cache)}
    assert any(cache.iterdir()), "nothing was written to the cache"


def test_default_is_the_checkout_cache():
    out = _run({})
    want = os.path.join(ROOT, ".jax_cache")
    assert out == {"returned": want, "config": want}
