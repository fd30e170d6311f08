"""Per-architecture smoke tests (REQUIRED by the assignment): a REDUCED
variant of each family runs one forward AND one GRPO train step on CPU,
asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import TrainConfig
from repro.configs import ASSIGNED_ARCHS, get_config, get_smoke_config
from repro.core.copris import make_train_step
from repro.models import model as M
from repro.optim import adam


def _media_for(cfg, key, batch):
    if not cfg.uses_media:
        return None
    xa = cfg.cross_attn
    return jax.random.normal(key, (batch, xa.num_media_tokens, xa.d_media),
                             jnp.float32) * 0.1


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_shapes_no_nans(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    logits, aux = M.forward_train(params, cfg, toks,
                                  media=_media_for(cfg, key, B), remat=False)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    assert jnp.isfinite(aux["router_aux"])


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = M.init_params(key, cfg)
    opt = adam.init(params)
    tcfg = TrainConfig(lr=1e-4, microbatches=1, remat=False)
    step = make_train_step(cfg, tcfg)
    B, S = 4, 16
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32).at[:, :4].set(0.0),
        "behaviour_logp": -jnp.abs(jax.random.normal(key, (B, S))),
        "advantages": jnp.asarray([1.0, -1.0, 0.5, -0.5]),
    }
    if cfg.uses_media:
        batch["media"] = _media_for(cfg, key, B)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch,
                                                 jnp.asarray(1e-4))
    assert jnp.isfinite(metrics["pg_loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    # parameters actually moved
    moved = jax.tree.reduce(
        lambda a, b: a or b,
        jax.tree.map(lambda o, n: bool(jnp.any(o != n)), params, new_params))
    assert moved


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-2b", "rwkv6-1.6b",
                                  "hymba-1.5b", "deepseek-moe-16b",
                                  "llama-3.2-vision-90b", "musicgen-medium",
                                  "qwen3-14b", "qwen3-moe-235b-a22b",
                                  "granite-34b"])
def test_decode_matches_full_forward(arch):
    """Prefill (ragged, right-padded) + incremental decode must reproduce the
    full-sequence forward logits — validates KV/state threading per family."""
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(2)
    params = M.init_params(key, cfg)
    B, S = 2, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    media = _media_for(cfg, key, B)
    full, _ = M.forward_train(params, cfg, toks, media=media, remat=False)

    lengths = jnp.array([5, 3])
    cache = M.init_cache(cfg, B, 32)
    lg, cache = M.prefill(params, cfg, toks, lengths, cache, media=media)
    for b, l in enumerate([5, 3]):
        np.testing.assert_allclose(lg[b], full[b, l - 1], atol=5e-3)
    cache_len = lengths
    for _ in range(4):
        tok = jax.vmap(lambda t, i: t[i])(toks, cache_len)
        lg, cache = M.decode_step(params, cfg, tok, cache, cache_len,
                                  media=media)
        for b in range(B):
            pos = int(cache_len[b])
            if pos + 1 <= S:
                np.testing.assert_allclose(lg[b], full[b, pos], atol=5e-3)
        cache_len = cache_len + 1


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, jit, pallas) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_pallas_decode_reads_dense_cache_in_place():
    """The decode-attention kernel reads the dense cache as stored: no
    transpose or pad of a cache-sized array runs on the decode step."""
    cfg = get_config("tiny")
    B, L = 2, 256
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = M.init_cache(cfg, B, L)
    layer = cache["body"][0]["k"].shape[1:]
    assert layer == (B, L, cfg.num_kv_heads * cfg.head_dim)
    jaxpr = jax.make_jaxpr(
        lambda p, c, t, n: M.decode_step(p, cfg, t, c, n, use_pallas=True))(
        params, cache, jnp.zeros((B,), jnp.int32),
        jnp.array([3, 200], jnp.int32)).jaxpr
    eqns = list(_eqns(jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    relayouts = [(e.primitive.name, v.aval.shape) for e in eqns
                 if e.primitive.name in ("transpose", "pad")
                 for v in e.invars
                 if L in getattr(v.aval, "shape", ())
                 and np.prod(v.aval.shape) >= np.prod(layer)]
    assert relayouts == []
