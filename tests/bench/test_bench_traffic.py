"""The shaped weights give the stated per-token stop probability, and the
traffic generator and reward behave as the traffic file says."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench_tiny import TINY_CFG, TINY_TRAFFIC
from bench.traffic import TrafficTask, parity_reward


@pytest.mark.parametrize("tied", [False, True])
def test_stop_probability(tied):
    from repro.models import model as M
    cfg = dict(TINY_CFG, tie_word_embeddings=tied, hidden_size=128,
               intermediate_size=256, vocab_size=2048, num_hidden_layers=3)
    tf = dict(TINY_TRAFFIC, stop_prob=1 / 256)
    mcfg = spec.model_config(cfg, "tiny")
    params = weights.make_params(cfg, tf, jnp.asarray(weights.np_key(7)))
    rng = np.random.default_rng(0)
    toks = rng.integers(2, cfg["vocab_size"], (16, 96)).astype(np.int32)
    logits, _ = M.forward_train(params, mcfg, jnp.asarray(toks), remat=False)
    p = np.asarray(jax.nn.softmax(logits, -1)[..., tf["eos_id"]])
    want = weights.design(cfg, tf)["stop_prob_effective"]
    assert want == pytest.approx(tf["stop_prob"], rel=0.02)
    # every position stops with about the same probability: the spread
    # across 1536 contexts is small, and their mean is the stated one
    assert p.mean() == pytest.approx(want, rel=0.05)
    assert p.std() / p.mean() < 0.15
    # and the rest of the distribution is a near-flat background
    ent = -(jax.nn.log_softmax(logits, -1) * jax.nn.softmax(logits, -1)).sum(-1)
    assert float(ent.mean()) > np.log(cfg["vocab_size"]) - 0.2


def test_prompts_and_reward():
    a = TrafficTask(TINY_TRAFFIC, 512, 2 ** 31 + 11)
    b = TrafficTask(TINY_TRAFFIC, 512, 2 ** 31 + 11)
    for _ in range(50):
        (pa, _), (pb, _) = a.sample_prompt(), b.sample_prompt()
        assert np.array_equal(pa, pb)
        assert TINY_TRAFFIC["prompt_len_min"] <= len(pa) <= \
            TINY_TRAFFIC["prompt_len_max"]
        assert TINY_TRAFFIC["eos_id"] not in pa
    assert a.prompts == 50 and a.prompt_tokens > 0
    rng = np.random.default_rng(1)
    r = [parity_reward(rng.integers(0, 512, 30), 1) for _ in range(2000)]
    assert 0.45 < np.mean(r) < 0.55
    assert parity_reward([2, 4, 1], 1) == 1.0      # the stop token is left out
    assert parity_reward([3, 1], 1) == 0.0


def test_expected_lengths():
    e = weights.expected_lengths(1 / 1024, 2048)
    assert e["mean"] == pytest.approx(885, abs=1)
    assert e["truncated"] == pytest.approx(0.135, abs=0.001)
    rng = np.random.default_rng(2)
    n = np.minimum(rng.geometric(1 / 1024, 200000), 2048)
    assert n.mean() == pytest.approx(e["mean"], rel=0.01)
    assert np.percentile(n, 50) == pytest.approx(e["p50"], abs=10)
