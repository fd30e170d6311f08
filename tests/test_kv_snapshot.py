"""kv_snapshot resume strategy: with UNCHANGED params it must be exactly
equivalent to re-prefill (same slot state -> same logits); the engine runs
end-to-end and actually restores snapshots."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import RolloutConfig
from repro.configs import get_config
from repro.core.rollout import RolloutEngine
from repro.data.tasks import AdditionTask, EOS
from repro.models import model as M
from repro.sampling import kv_cache as kvc

CFG = get_config("tiny")


def test_snapshot_roundtrip_equals_reprefill():
    """Extract slot 1's state, insert into a fresh pool, decode — logits
    must equal both the uninterrupted run AND a re-prefill of the tokens."""
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    B, P = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 24), 0, CFG.vocab_size)
    lengths = jnp.array([P, P - 2])
    cache = M.init_cache(CFG, B, 48)
    _, cache = M.prefill(params, CFG, toks[:, :P], lengths, cache)
    cl = lengths
    for s in range(4):                       # decode 4 ground-truth tokens
        tok = jax.vmap(lambda t, i: t[i])(toks, cl)
        ref_logits, cache = M.decode_step(params, CFG, tok, cache, cl)
        cl = cl + 1

    # snapshot slot 1, restore into a fresh 3-slot pool at slot 2
    snap = kvc.extract_slots(cache, jnp.asarray([1]))
    pool = M.init_cache(CFG, 3, 48)
    pool = kvc.insert_slots(pool, snap, jnp.asarray([2]))
    tok = jax.vmap(lambda t, i: t[i])(toks, cl)
    got, _ = M.decode_step(params, CFG, jnp.asarray([0, 0, tok[1]]), pool,
                           jnp.asarray([1, 1, int(cl[1])]))
    want, _ = M.decode_step(params, CFG, tok, cache, cl)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[1]),
                               atol=2e-5)


def test_engine_kv_snapshot_mode():
    task = AdditionTask(max_value=20, seed=11)
    # eviction by construction: twice the slots B*G needs, and a length cap
    # high enough that random EOS ends responses at different steps — the
    # stage closes on its B-th group while other slots are mid-response
    ro = RolloutConfig(batch_size=3, group_size=2, max_prompt_len=16,
                       max_response_len=128, concurrency=12, mode="copris",
                       resume_strategy="kv_snapshot")
    params = M.init_params(jax.random.PRNGKey(2), CFG)
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
    g1, s1 = eng.collect(params, 0, jax.random.PRNGKey(3))
    assert s1["evicted"] > 0
    # evicted trajectories must carry snapshots
    snaps = [t for g in eng.buffer.groups() for t in g.trajectories
             if t.kv_snapshot is not None]
    assert snaps, "evicted partials should hold kv snapshots"
    g2, s2 = eng.collect(params, 1, jax.random.PRNGKey(4))
    assert s2.get("snapshot_resumes", 0) > 0, "snapshots must be restored"
    assert len(g2) == ro.batch_size
    for g in g2:
        for t in g.trajectories:
            t.check_invariants()
