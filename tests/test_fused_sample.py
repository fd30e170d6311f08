"""Fused on-device top-k/top-p sampling (PR 10 tentpole b): the Pallas
kernel regenerates jax's threefry Gumbel bits and radix-finds the
truncation thresholds, so its TOKEN stream is bit-identical to
``sampler.sample_rows`` (the XLA oracle) — the determinism contract the
chunked rollout engine is built on. logps are allclose (not bitwise: the
kernel's blocked logsumexp sums in a different order)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_sample import ops as fs_ops
from repro.sampling import sampler

# (temperature, top_p, top_k) — covers plain, tempered, k-only, p-only,
# combined, and aggressive truncation
CONFIGS = [
    (1.0, 1.0, -1),
    (0.7, 1.0, -1),
    (1.0, 1.0, 5),
    (1.0, 0.9, -1),
    (0.8, 0.95, 40),
    (1.3, 0.5, 3),
]


def _logits(key, B, V, scale=4.0):
    return jax.random.normal(jax.random.PRNGKey(key), (B, V)) * scale


@pytest.mark.parametrize("V", [7, 100, 2049])   # odd V: padded last block
@pytest.mark.parametrize("cfg", CONFIGS)
def test_token_bit_identity(V, cfg):
    temperature, top_p, top_k = cfg
    B = 16
    keys = jax.random.split(jax.random.PRNGKey(V), B)
    logits = _logits(V + 1, B, V)
    t_ref, lp_ref = sampler.sample_rows(keys, logits, temperature=temperature,
                                        top_p=top_p, top_k=top_k)
    t_fus, lp_fus = fs_ops.fused_sample_rows(
        keys, logits, temperature=temperature, top_p=top_p, top_k=top_k,
        block_rows=8, block_v=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(t_fus), np.asarray(t_ref))
    np.testing.assert_allclose(np.asarray(lp_fus), np.asarray(lp_ref),
                               atol=1e-5)


def test_top_k_equals_vocab_minus_one():
    """k = V-1 drops exactly the worst token — exercises the radix top-k
    boundary where the count bin holds a single element."""
    B, V = 8, 257
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    logits = _logits(3, B, V)
    t_ref, _ = sampler.sample_rows(keys, logits, top_k=V - 1)
    t_fus, _ = fs_ops.fused_sample_rows(keys, logits, top_k=V - 1,
                                        block_rows=8, block_v=64,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(t_fus), np.asarray(t_ref))


def test_near_ties_and_neg_inf_rows():
    """Duplicate logit values straddling the top-k threshold (ties kept on
    both sides, matching ``prepare_logits``) and rows dominated by one
    huge logit."""
    B, V = 8, 96
    base = _logits(11, B, V, scale=1.0)
    base = jnp.round(base * 4) / 4          # force exact duplicates
    base = base.at[0].set(jnp.full((V,), -1e4).at[7].set(50.0))
    keys = jax.random.split(jax.random.PRNGKey(5), B)
    for temperature, top_p, top_k in [(1.0, 1.0, 8), (1.0, 0.8, -1),
                                      (0.5, 0.9, 16)]:
        t_ref, _ = sampler.sample_rows(keys, base, temperature=temperature,
                                       top_p=top_p, top_k=top_k)
        t_fus, _ = fs_ops.fused_sample_rows(
            keys, base, temperature=temperature, top_p=top_p, top_k=top_k,
            block_rows=8, block_v=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(t_fus), np.asarray(t_ref))


def test_greedy_path():
    B, V = 8, 64
    keys = jax.random.split(jax.random.PRNGKey(1), B)
    logits = _logits(2, B, V)
    tok, logp = fs_ops.fused_sample_rows(keys, logits, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.asarray(jnp.argmax(logits, axis=-1)))
    assert np.all(np.asarray(logp) == 0.0)
    assert tok.dtype == jnp.int32


def test_row_purity_matches_oracle():
    """Row i's draw depends only on (keys[i], logits[i]) — permuting the
    batch permutes the outputs (the property the engine's slot assignment
    relies on)."""
    B, V = 12, 130
    keys = jax.random.split(jax.random.PRNGKey(4), B)
    logits = _logits(9, B, V)
    tok, lp = fs_ops.fused_sample_rows(keys, logits, top_p=0.95, top_k=17,
                                       block_rows=4, block_v=64,
                                       interpret=True)
    perm = np.random.RandomState(0).permutation(B)
    tok_p, lp_p = fs_ops.fused_sample_rows(
        keys[perm], logits[perm], top_p=0.95, top_k=17, block_rows=4,
        block_v=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(tok_p), np.asarray(tok)[perm])
    np.testing.assert_allclose(np.asarray(lp_p), np.asarray(lp)[perm],
                               atol=1e-6)


def test_logp_is_truncated_distribution():
    """The returned logp is log-prob under the TRUNCATED distribution
    (what CoPRIS buffers as the behaviour logp), not the raw softmax."""
    B, V = 8, 200
    keys = jax.random.split(jax.random.PRNGKey(8), B)
    logits = _logits(13, B, V)
    tok, lp = fs_ops.fused_sample_rows(keys, logits, top_k=10,
                                       block_rows=8, block_v=64,
                                       interpret=True)
    l = sampler.prepare_logits(logits, temperature=1.0, top_k=10)
    want = jnp.take_along_axis(jax.nn.log_softmax(l, axis=-1),
                               tok[:, None], axis=1)[:, 0]
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want), atol=1e-5)


# -- the engine-level pin: chunked-decode bit-identity survives -------------


def _run_engine(params, chunk, fused: bool, monkeypatch):
    """sync-mode collect; ``fused`` swaps ONLY the sampler (model math stays
    on XLA so the pin isolates the new kernel)."""
    from repro.common.config import RolloutConfig
    from repro.core import rollout as rollout_mod
    from repro.core.rollout import RolloutEngine
    from repro.data.tasks import AdditionTask, EOS

    if fused:
        wrapped = functools.partial(fs_ops.fused_sample_rows,
                                    block_rows=4, block_v=64, interpret=True)
        monkeypatch.setattr(rollout_mod.sampler, "sample_rows", wrapped)
    task = AdditionTask(max_value=20, seed=9)
    ro = RolloutConfig(batch_size=2, group_size=2, max_prompt_len=16,
                       max_response_len=12, concurrency=4, mode="sync",
                       decode_chunk=chunk, temperature=1.0, top_p=0.9,
                       top_k=8)
    from repro.configs import get_config
    eng = RolloutEngine(get_config("tiny"), ro, task.sample_prompt,
                        eos_id=EOS)
    groups, _ = eng.collect(params, 0, jax.random.PRNGKey(42))
    return {(g.group_id, t.sample_idx): t
            for g in groups for t in g.trajectories}


@pytest.mark.slow
def test_engine_chunked_bit_identity_with_fused_sampler(monkeypatch):
    """PR 1's decode_chunk-invariance contract survives the fused sampler:
    the engine produces the SAME trajectories (tokens and behaviour logps)
    with the XLA sampler at chunk=1 and the fused kernel at chunk∈{1,4}."""
    from repro.configs import get_config
    from repro.models import model as M
    params = M.init_params(jax.random.PRNGKey(0), get_config("tiny"))
    base = _run_engine(params, 1, False, monkeypatch)
    assert base, "baseline produced no trajectories"
    for chunk in (1, 4):
        got = _run_engine(params, chunk, True, monkeypatch)
        assert set(got) == set(base)
        for key in base:
            tb, tg = base[key], got[key]
            assert tb.response_tokens == tg.response_tokens, key
            assert np.allclose(tb.behaviour_logps, tg.behaviour_logps,
                               atol=1e-5), key
            assert tb.finish_reason == tg.finish_reason, key
