"""The float32 reference against the system's own forward and loss, at a
tiny size on the CPU (both in float32, so they agree to rounding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from bench_tiny import TINY_CFG, TINY_TRAFFIC


@pytest.mark.parametrize("tied,qk_norm", [(False, False), (True, True)])
def test_reference_matches_model(tied, qk_norm):
    from repro.common.config import TrainConfig
    from repro.core.copris import make_loss_fn
    from repro.models import model as M
    cfg = dict(TINY_CFG, tie_word_embeddings=tied, qk_norm=qk_norm,
               torch_dtype="float32")
    fam = spec.family(cfg)
    mcfg = fam.model_config(cfg, "tiny")
    params = fam.make_params(cfg, TINY_TRAFFIC,
                             jnp.asarray(weights.np_key(5)))
    rng = np.random.default_rng(3)
    N, T = 4, 40
    toks = rng.integers(2, cfg["vocab_size"], (N, T)).astype(np.int32)
    logits, _ = M.forward_train(params, mcfg, jnp.asarray(toks[:, :-1]),
                                remat=False)
    lp_model = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   jnp.asarray(toks[:, 1:, None]), -1)[..., 0]
    cs = reference.static_of(cfg)
    lp_ref = reference._logp_block(cs, "highest", params, jnp.asarray(toks))
    np.testing.assert_allclose(lp_ref, lp_model, atol=2e-4)

    mask = np.zeros((N, T), np.float32)
    mask[:, 12:] = 1.0
    beh = np.asarray(lp_model) + rng.normal(0, 0.1, (N, T - 1))
    beh = np.concatenate([np.zeros((N, 1)), beh], 1).astype(np.float32)
    adv = rng.normal(0, 1, N).astype(np.float32)
    tf = TINY_TRAFFIC
    tc = TrainConfig(clip_low=tf["clip_low"], clip_high=tf["clip_high"],
                     is_ratio_cap=tf["is_ratio_cap"], remat=False)
    loss_fn = make_loss_fn(mcfg, tc)
    mb = {"tokens": jnp.asarray(toks), "loss_mask": jnp.asarray(mask),
          "behaviour_logp": jnp.asarray(beh), "advantages": jnp.asarray(adv)}
    (l_sys, _), g_sys = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
    hy = (tf["clip_low"], tf["clip_high"], tf["is_ratio_cap"])
    l_ref, _, g_ref = reference._loss_block(
        cs, "highest", hy, params, jnp.asarray(toks),
        jnp.asarray(mask[:, 1:]), jnp.asarray(beh[:, 1:]),
        jnp.zeros((N, T - 1), bool), jnp.asarray(adv),
        np.float32(mask[:, 1:].sum()))
    assert float(l_ref) == pytest.approx(float(l_sys), rel=1e-4, abs=1e-6)
    # element 0 of the final norm's scale has a gradient that is zero in
    # exact arithmetic (that coordinate's scale is 0, and with a tied head
    # its head row is the same constant for every token): rounding alone
    g_ref["final_norm"] = g_ref["final_norm"].at[0].set(0.0)
    g_sys["final_norm"] = g_sys["final_norm"].at[0].set(0.0)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sys)):
        assert float(jnp.linalg.norm(a - b)) <= 2e-3 * float(
            jnp.linalg.norm(b)) + 1e-7


def test_block_rows():
    """A block's attention scores within 1 GiB and its float32 logits
    within 3 GiB: at every train length of the longtail from 1728 up the
    rows the scores alone gave (so its reference is unchanged), 8 at the
    short mix's 704, where the scores alone would allow 32 rows and 6.8 GB
    of logits."""
    cfg = spec.load_config(spec.load_benchmark(), "r1-distill-qwen-1.5b")
    for T in range(1728, 2497, 64):
        n = (1 << 30) // (cfg["num_attention_heads"] * T * T * 4)
        assert reference.block_rows(cfg, T) == 1 << (n.bit_length() - 1), T
    assert reference.block_rows(cfg, 2496) == 2
    assert reference.block_rows(cfg, 704) == 8
    assert reference.block_rows(TINY_CFG, 40) == 1 << 15


def test_group_advantages():
    a = reference.group_advantages([1, 0, 1, 0, 1, 1, 1, 1], 4)
    np.testing.assert_allclose(a[:4], [1, -1, 1, -1], atol=1e-5)
    np.testing.assert_allclose(a[4:], 0, atol=1e-6)
