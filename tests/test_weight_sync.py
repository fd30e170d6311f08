"""Versioned weight-sync (ParamStore) + disaggregated reshard.

* ParamStore contract: strict version monotonicity, Laminar-style
  drop-stale eviction of superseded versions, acquire-freshest, wait_for;
* reshard round-trip: train shardings (FSDP data+model) -> rollout
  ``serve_tp_only`` shardings on the CPU mesh leaves every value bitwise
  intact — the sync moves bytes, never rewrites them;
* disaggregated trainer: the resharded params the rollout side acquires
  are leaf-wise identical to the version the consumer published at every
  stage;
* config validation: disaggregated requires overlap, with an actionable
  message.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import RolloutConfig, TrainConfig
from repro.configs import get_config
from repro.core.copris import CoPRISTrainer
from repro.core.weight_sync import ParamStore, make_param_resharder
from repro.data.tasks import AdditionTask, EOS
from repro.launch.mesh import make_cpu_mesh
from repro.models import model as M

CFG = get_config("tiny")


# ---------------------------------------------------------------------------
# ParamStore unit tests
# ---------------------------------------------------------------------------


def test_param_store_publish_acquire_freshest():
    ps = ParamStore(max_versions=3)
    assert ps.latest_version == -1
    for v in range(3):
        ps.publish({"w": v}, v)
    params, version = ps.acquire()
    assert version == 2 and params == {"w": 2}
    assert ps.versions() == (0, 1, 2)
    assert ps.stats["published"] == 3 and ps.stats["acquired"] == 1


def test_param_store_version_monotonicity():
    ps = ParamStore(max_versions=4)
    ps.publish({"w": 0}, 5)
    with pytest.raises(ValueError, match="monotonic"):
        ps.publish({"w": 1}, 5)           # same version, no replace
    with pytest.raises(ValueError, match="monotonic"):
        ps.publish({"w": 1}, 3)           # older version
    # checkpoint-restore swaps the weights behind the unchanged version
    ps.publish({"w": "restored"}, 5, replace=True)
    params, version = ps.acquire()
    assert version == 5 and params == {"w": "restored"}
    with pytest.raises(ValueError, match="monotonic"):
        ps.publish({"w": 2}, 4, replace=True)   # replace can't rewind


def test_param_store_drop_stale():
    ps = ParamStore(max_versions=2)
    for v in range(5):
        ps.publish({"w": v}, v)
    assert ps.versions() == (3, 4)        # bounded window, oldest dropped
    assert ps.stats["dropped"] == 3
    assert ps.get(4) == {"w": 4}
    with pytest.raises(KeyError):
        ps.get(0)                          # superseded weights are gone
    _, version = ps.acquire()
    assert version == 4


def test_param_store_acquire_before_publish():
    with pytest.raises(RuntimeError, match="before the first publish"):
        ParamStore().acquire()


def test_param_store_rejects_empty_window():
    with pytest.raises(ValueError, match="max_versions"):
        ParamStore(max_versions=0)


def test_param_store_wait_for():
    ps = ParamStore(max_versions=2)
    ps.publish({"w": 0}, 0)
    assert ps.wait_for(0, timeout=0.1)
    assert not ps.wait_for(1, timeout=0.05)     # not there yet

    def late_publish():
        ps.publish({"w": 1}, 1)
    t = threading.Timer(0.05, late_publish)
    t.start()
    try:
        assert ps.wait_for(1, timeout=5.0)      # unblocked by the publish
    finally:
        t.join()


def test_param_store_reshard_hook_applied():
    calls = []

    def reshard(tree):
        calls.append(tree)
        return {k: v + 100 for k, v in tree.items()}

    ps = ParamStore(max_versions=2, reshard=reshard)
    ps.publish({"w": 1}, 0)
    params, _ = ps.acquire()
    assert params == {"w": 101} and len(calls) == 1
    assert ps.stats["reshard_time"] >= 0.0


# ---------------------------------------------------------------------------
# reshard round-trip (train layout -> rollout serve_tp_only layout)
# ---------------------------------------------------------------------------


def test_reshard_round_trip_bitwise_identical():
    mesh = make_cpu_mesh()
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    reshard, out_sh = make_param_resharder(CFG, params, mesh)
    out = reshard(params)
    flat_in, tree_in = jax.tree_util.tree_flatten(params)
    flat_out, tree_out = jax.tree_util.tree_flatten(out)
    assert tree_in == tree_out
    for a, b in zip(flat_in, flat_out):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the output actually carries the rollout shardings
    for leaf, sh in zip(flat_out, jax.tree.leaves(out_sh)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_reshard_serve_tp_only_drops_data_axis():
    """On a mesh with a real FSDP axis the rollout layout must not shard
    any weight over "data" — inference replicates the FSDP axis so decode
    never pays per-step weight all-gathers."""
    from repro.launch import sharding as shd

    params = jax.eval_shape(lambda k: M.init_params(k, CFG),
                            jax.random.PRNGKey(0))
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((16, 16), ("data", "model"))
    out_sh = shd.params_shardings(params, mesh, serve_tp_only=True, cfg=CFG)
    for sh in jax.tree.leaves(out_sh):
        flat_axes = []
        for ax in sh.spec:
            flat_axes.extend(ax if isinstance(ax, tuple) else (ax,))
        assert "data" not in flat_axes, sh


@pytest.mark.slow
def test_reshard_across_disjoint_device_sets():
    """True disaggregation: train and rollout meshes over DISJOINT device
    sets (8 fake host devices, 4+4). The reshard becomes a device-to-device
    transfer (jax.device_put) — values bitwise intact, output resident on
    the rollout mesh's devices only."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np
from repro.configs import get_config
from repro.core.weight_sync import make_param_resharder
from repro.launch.mesh import make_disaggregated_meshes
from repro.models import model as M

cfg = get_config("tiny")
train_mesh, rollout_mesh = make_disaggregated_meshes((2, 2), (2, 2))
assert not (set(d.id for d in train_mesh.devices.flat)
            & set(d.id for d in rollout_mesh.devices.flat))
from repro.launch import sharding as shd
params = M.init_params(jax.random.PRNGKey(0), cfg)
params = jax.device_put(
    params, shd.params_shardings(params, train_mesh, cfg=cfg))
reshard, out_sh = make_param_resharder(cfg, params, train_mesh,
                                       rollout_mesh)
out = reshard(params)
rollout_ids = set(d.id for d in rollout_mesh.devices.flat)
ok = True
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
    ok &= bool(np.array_equal(np.asarray(a), np.asarray(b)))
    ok &= set(d.id for d in b.sharding.device_set) <= rollout_ids
print(json.dumps({"ok": bool(ok),
                  "n_leaves": len(jax.tree.leaves(out))}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["n_leaves"] > 0


# ---------------------------------------------------------------------------
# disaggregated trainer: resharded == published, at every stage
# ---------------------------------------------------------------------------


def _tree_equal(a, b) -> bool:
    eq = jax.tree.map(lambda x, y: bool(np.array_equal(np.asarray(x),
                                                       np.asarray(y))), a, b)
    return all(jax.tree.leaves(eq))


@pytest.mark.slow
def test_disaggregated_trainer_params_identical(tiny_trainer_params):
    """overlap=True + disaggregated=True on the CPU mesh: every version the
    store serves is leaf-wise identical to the consumer's params at that
    stage (the reshard moves bytes between layouts, never rewrites them)."""
    ro = RolloutConfig(batch_size=4, group_size=2, max_prompt_len=16,
                       max_response_len=12, concurrency=8, mode="copris")
    tc = TrainConfig(lr=2e-4, warmup_steps=2, microbatches=1,
                     overlap=True, disaggregated=True, seed=0)
    tr = CoPRISTrainer(CFG, ro, tc, AdditionTask(max_value=9, seed=0),
                       eos_id=EOS,
                       params=jax.tree.map(jnp.copy, tiny_trainer_params))
    tr.batch_timeout = 120.0
    try:
        for _ in range(3):
            out = tr.step()
            assert np.isfinite(out["pg_loss"])
            assert out["param_staleness"] <= tr.max_staleness
            assert out["reshard_time"] >= 0.0
            # the store's freshest version IS the consumer's current params
            stored = tr.param_store.get(tr.stage)
            assert _tree_equal(stored, tr.params)
    finally:
        tr.close()


@pytest.fixture(scope="module")
def tiny_trainer_params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_disaggregated_requires_overlap():
    with pytest.raises(ValueError, match="requires overlap=True"):
        TrainConfig(disaggregated=True)
    TrainConfig(disaggregated=True, overlap=True)   # valid


def test_trainer_restore_republishes():
    ro = RolloutConfig(batch_size=4, group_size=2, max_prompt_len=16,
                       max_response_len=12, concurrency=8, mode="copris")
    tr = CoPRISTrainer(CFG, ro, TrainConfig(seed=0),
                       AdditionTask(max_value=9, seed=0), eos_id=EOS)
    try:
        new_params = jax.tree.map(lambda x: x + 1.0, tr.params)
        tr.restore(params=new_params, stage=3)
        params, version = tr.param_store.acquire()
        assert version == 3
        assert _tree_equal(params, new_params)
        with pytest.raises(ValueError, match="rewind"):
            tr.restore(stage=1)
    finally:
        tr.close()
