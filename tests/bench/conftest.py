import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """A benchmark run turns on the persistent compilation cache in its
    root; give the process back its own settings afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    yield
    if any(getattr(jax.config, k) != v for k, v in was.items()):
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
