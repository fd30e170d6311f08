"""Seeded random weights with a shaped stop token, for the ``qwen2``
family (``bench/families/qwen2.py``).

With random weights no response would ever stop, so the benchmark imposes
the response-length distribution through the weights themselves. Two
residual coordinates are constants that no layer writes to or reads (the
layers' output projections have zero columns there, their input norms zero
scales): coordinate 0 holds ``ALPHA``, large enough that every norm's
divisor is the same at every position, and reaches no logit (zero final
scale); coordinate 1 holds 1 and comes out of the final norm as 1 at every
position. The stop token's head weight on coordinate 1 puts its logit a
fixed ``offset`` above a flat background of logits with standard deviation
``logit_std``, so every sampled token ends the response with the same
probability ``stop_prob``: lengths are geometric up to the cap. No weight
is far from unit scale, so no gradient is either and clipping stays
inactive.

The pytree is the layout the trainer takes (``embed``, ``stack.body`` with
a leading layer axis, ``final_norm``, ``lm_head`` when untied); it is made
on the device in one jitted call, in the trainer's master dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1024.0         # the constant residual coordinate (a power of two)


def design(cfg: dict, traffic: dict) -> dict:
    """The shaping constants for a configuration file and a traffic mix.

    ``offset`` is the stop logit's height over the background; its
    bfloat16-rounded value is what the trainer's bf16 head computes, and the
    probability that offset gives is ``stop_prob_effective``."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    p, sigma = traffic["stop_prob"], traffic["logit_std"]
    offset = math.log(p / (1 - p)) + math.log(V - 1) + sigma ** 2 / 2
    # tied: every token's coordinate-1 embedding is 1, which is also its
    # head weight there: every logit moves by 1, the stop token's by more
    base = 1.0 if cfg["tie_word_embeddings"] else 0.0
    eos_w = float(np.asarray(base + offset, jnp.bfloat16))
    eff = eos_w - base
    lse = math.log(V - 1) + sigma ** 2 / 2
    p_eff = 1.0 / (1.0 + math.exp(lse - eff))
    # the residual stream's other coordinates grow by about one unit of
    # variance per sub-block (unit-scale inputs, 1/sqrt(fan_in) weights)
    rho = math.sqrt(1.0 + 2.0 * L)
    return dict(offset=eff, stop_prob_effective=p_eff, eos_w=eos_w,
                base=base, final_scale=sigma * ALPHA / (d * rho),
                norm_scale=ALPHA / math.sqrt(d))


def make_params(cfg: dict, traffic: dict, key):
    """Params for ``cfg`` from ``key``, on the default device."""
    return _make(_static(cfg, traffic), key)


def _static(cfg, traffic):
    dz = design(cfg, traffic)
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], bool(cfg["qk_norm"]),
            bool(cfg["tie_word_embeddings"]), int(traffic["eos_id"]),
            dz["eos_w"], dz["base"], dz["final_scale"], dz["norm_scale"],
            cfg["param_dtype"])


@functools.partial(jax.jit, static_argnums=0)
def _make(static, key):
    (d, h, kv, hd, ff, V, L, qk_norm, tied, eos, eos_w, base, fscale,
     nscale, pdt) = static
    dtype = jnp.dtype(pdt)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def zero_col0(w):                # layers never write coordinates 0, 1
        return w.at[..., :2].set(0.0)

    ln = jnp.full((L, d), nscale, jnp.float32).at[:, :2].set(0.0)
    attn = {"wq": normal((L, d, h * hd), d ** -0.5),
            "wk": normal((L, d, kv * hd), d ** -0.5),
            "wv": normal((L, d, kv * hd), d ** -0.5),
            "wo": zero_col0(normal((L, h * hd, d), (h * hd) ** -0.5))}
    if qk_norm:
        attn["q_norm"] = jnp.ones((L, hd), jnp.float32)
        attn["k_norm"] = jnp.ones((L, hd), jnp.float32)
    mlp = {"wi": normal((L, d, ff), d ** -0.5),
           "wg": normal((L, d, ff), d ** -0.5),
           "wo": zero_col0(normal((L, ff, d), ff ** -0.5))}
    block = {"ln1": ln, "ln2": ln, "attn": attn, "mlp": mlp}

    tok = normal((V, d), 1.0).at[:, 0].set(ALPHA).at[:, 1].set(1.0)
    # the norms divide by ALPHA / sqrt(d): coordinate 1 leaves the final
    # norm as 1, coordinate 0 as 0
    final = (jnp.full((d,), fscale, jnp.float32).at[0].set(0.0)
             .at[1].set(ALPHA / math.sqrt(d)))
    params = {"embed": {"tok": tok}, "stack": {"prefix": [], "body": (block,)},
              "final_norm": final}
    if tied:
        # the head is tok.T: its row 1 shifts every logit by 1, the stop
        # row's coordinate 1 lifts its logit by ``offset``; it has no
        # background component, so its logit carries no noise
        params["embed"]["tok"] = (tok.at[eos, 2:].set(0.0)
                                  .at[eos, 1].set(eos_w))
    else:
        head = normal((d, V), 1.0).at[:2].set(0.0)
        params["lm_head"] = head.at[:, eos].set(0.0).at[1, eos].set(eos_w)
    return jax.tree.map(lambda a: a.astype(dtype), params)


def expected_lengths(stop_prob: float, cap: int) -> dict:
    """Mean, median, 95th percentile and truncated share of a geometric
    response length (per-token stop probability ``stop_prob``) capped at
    ``cap`` tokens."""
    q = 1.0 - stop_prob
    trunc = q ** cap
    mean = (1.0 - q ** cap) / stop_prob

    def quantile(f):
        n = math.ceil(math.log(1.0 - f) / math.log(q))
        return min(max(n, 1), cap)

    return dict(mean=mean, p50=quantile(0.5), p95=quantile(0.95),
                truncated=trunc)


def np_key(seed: int) -> np.ndarray:
    """A raw uint32 PRNG key from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)
