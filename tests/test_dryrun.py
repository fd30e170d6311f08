"""Dry-run smoke: lower+compile one (arch × shape) per step kind on the
256-device mesh in a subprocess (the 512-host-device XLA flag must be set
before jax initialises, hence not in-process)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_dryrun_decode_and_train_compile():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_one
out = []
out.append(run_one("llama3.2-1b", "decode_32k", verbose=False))
out.append(run_one("rwkv6-1.6b", "long_500k", verbose=False))
print(json.dumps([{k: r[k] for k in ("arch", "shape", "status", "dominant")}
                  for r in out]))
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    recs = json.loads(r.stdout.strip().splitlines()[-1])
    assert all(x["status"] == "ok" for x in recs), recs


@pytest.mark.slow
def test_dryrun_weight_sync_reshard_compiles():
    """The ParamStore reshard (train FSDP layout -> rollout serve_tp_only
    layout) lowers + compiles on the 256-device production mesh, and its
    collective bill is all-gather only: the sync pays the one FSDP weight
    gather per published version OFF the decode critical path — a reshard
    that lowers to anything else (e.g. per-leaf permutes from a bad spec)
    is a regression."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_reshard
r = run_reshard("llama3.2-1b", verbose=False)
print(json.dumps({"status": r["status"], "chips": r["chips"],
                  "coll": r["collective_bytes"],
                  "sync_bytes": r["sync_bytes_per_version"]}))
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok" and rec["chips"] == 256
    assert rec["sync_bytes"] > 0
    kinds = {k for k, v in rec["coll"].items() if k != "total" and v > 0}
    assert kinds == {"all-gather"}, rec["coll"]


@pytest.mark.slow
def test_dryrun_multipod_compiles():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_one
r = run_one("gemma2-2b", "decode_32k", multi_pod=True, verbose=False)
print(json.dumps({"status": r["status"], "chips": r.get("chips")}))
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok" and rec["chips"] == 512


def test_sharding_rules_all_archs():
    """param/cache specs are constructible and divisibility-safe for every
    assigned arch on an abstract 16x16 mesh (no device allocation)."""
    import jax
    import numpy as np
    from jax.sharding import AbstractMesh

    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.launch import sharding as shd
    from repro.models import model as M

    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        shape = jax.eval_shape(lambda k: M.init_params(k, cfg),
                               jax.random.PRNGKey(0))

        def check(path, leaf):
            spec = shd.param_pspec(path, leaf, mesh, cfg)
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert leaf.shape[i] % size == 0, (arch, path, spec)
        jax.tree_util.tree_map_with_path(check, shape)
