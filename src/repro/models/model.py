"""LM wrapper: embeddings → block stack → final norm → logits.

Public entry points (all pure functions over a params pytree):

* ``init_params(key, cfg)``
* ``forward_train(params, cfg, tokens, ...)`` — full-sequence logits (+aux)
* ``score_logprobs(params, cfg, tokens, ...)`` — per-token log p(token) under
  the current policy (the IS-recompute pass; uses the fused vocab-blocked
  path to avoid materialising (B, S, V) probabilities)
* ``prefill(params, cfg, tokens, lengths, cache, ...)`` — seed the slot cache,
  return last-valid-position logits
* ``decode_step(params, cfg, token, cache, cache_len, ...)`` — one token
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import transformer
from repro.models.layers import embed_init, dense_init, rms_norm, softcap
from repro.models.transformer import _gather_last


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_e, k_s, k_h, k_m = jax.random.split(key, 4)
    params = {
        "embed": {"tok": embed_init(k_e, (cfg.vocab_size, cfg.d_model), dtype)},
        "stack": transformer.init_stack(k_s, cfg, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_h, (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.uses_media:
        params["embed"]["media_proj"] = dense_init(
            k_m, (cfg.cross_attn.d_media, cfg.d_model), dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return transformer.init_stack_cache(cfg, batch, max_len, dtype)


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int, num_pages: int, dtype=None):
    """Paged-KV slot cache: attention K/V leaves become physical page pools
    (num_pages, page_size, kv, hd) shared by all ``batch`` slots (layer-
    stacked body leaves carry a leading repeats axis); non-attention state
    keeps its per-slot batch axis. Decode with ``decode_step(...,
    paged=(block_table, page_size))``."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    return transformer.init_stack_cache(cfg, batch, max_len, dtype,
                                        kv_pages=(num_pages, page_size))


# ---------------------------------------------------------------------------


def _embed(params, cfg: ModelConfig, tokens):
    dt = jnp.dtype(cfg.dtype)
    tab = params["embed"]["tok"]
    if cfg.embed_impl == "onehot":
        # one-hot matmul: SPMD partitions this like any other matmul
        # (vocab-parallel embedding without gather rematerialization)
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=dt)
        x = oh @ tab.astype(dt)
    else:
        x = tab[tokens].astype(dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    return x


def _project_media(params, cfg: ModelConfig, media, *, mode="train"):
    if media is None and cfg.uses_media and mode != "decode":
        # decode reads the cached media K/V (hillclimb C); other modes
        # require the (stubbed) frontend embeddings
        raise ValueError(f"{cfg.name} requires media embeddings")
    if media is None:
        return None
    return media.astype(jnp.dtype(cfg.dtype)) @ params["embed"]["media_proj"].astype(
        jnp.dtype(cfg.dtype))


def _logits(params, cfg: ModelConfig, x):
    w = unembed_weight(params, cfg)
    out = x @ w.astype(x.dtype)
    out = out.astype(jnp.float32)
    if cfg.logit_softcap > 0.0:
        out = softcap(out, cfg.logit_softcap)
    return out


def backbone(params, cfg: ModelConfig, tokens, *, positions=None, media=None,
             cache=None, cache_len=None, seq_mask=None, lengths=None,
             mode="train", use_pallas=False, remat=False, paged=None):
    """Embed + stack + final norm. Returns (hidden (B,S,d), new_cache, aux)."""
    B, S = tokens.shape
    if positions is None:
        if mode == "decode":
            positions = cache_len[:, None]
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = _embed(params, cfg, tokens)
    if mode != "decode":
        from repro.common.partitioning import shard_activation
        x = shard_activation(x, "dp", None, None)
    media_p = _project_media(params, cfg, media, mode=mode)
    x, new_cache, aux = transformer.apply_stack(
        params["stack"], cfg, x, positions=positions, media=media_p,
        cache=cache, cache_len=cache_len, seq_mask=seq_mask, lengths=lengths,
        mode=mode, use_pallas=use_pallas, remat=remat, paged=paged)
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return x, new_cache, aux


# -- training ---------------------------------------------------------------


def forward_train(params, cfg: ModelConfig, tokens, *, media=None,
                  seq_mask=None, remat=True):
    """Full logits (B, S, V) fp32 + aux dict."""
    x, _, aux = backbone(params, cfg, tokens, media=media, seq_mask=seq_mask,
                         mode="train", remat=remat)
    return _logits(params, cfg, x), {"router_aux": aux}


def unembed_weight(params, cfg: ModelConfig):
    """The (d, V) unembedding matrix (tied embedding or lm_head)."""
    return params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]


def forward_hidden(params, cfg: ModelConfig, tokens, *, media=None,
                   seq_mask=None, remat=True):
    """Backbone only: final-norm hidden states (B, S, d) + aux dict.

    The pre-unembedding entry point for fused losses (kernels/fused_is_grpo)
    that consume (hidden, unembed_weight) directly and never materialise
    the (B, S, V) logits."""
    x, _, aux = backbone(params, cfg, tokens, media=media, seq_mask=seq_mask,
                         mode="train", remat=remat)
    return x, {"router_aux": aux}


def token_logprobs_from_logits(logits, targets):
    """logits: (B, S, V) fp32; targets: (B, S) — log p(targets)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return tgt - lse


def score_logprobs(params, cfg: ModelConfig, tokens, targets, *, media=None,
                   seq_mask=None, remat=True, vocab_block: int = 0):
    """Per-token log-prob of ``targets`` given ``tokens`` (same length,
    targets[t] is the next-token label for position t). Memory-safe for huge
    vocabularies via the fused vocab-blocked gather-logsumexp path.
    Returns (logps (B, S) fp32, aux)."""
    x, _, aux = backbone(params, cfg, tokens, media=media, seq_mask=seq_mask,
                         mode="train", remat=remat)
    from repro.kernels.fused_logprob import ref as flp_ref
    lp = flp_ref.fused_logprob(x, unembed_weight(params, cfg), targets,
                               logit_softcap=cfg.logit_softcap,
                               vocab_block=vocab_block)
    return lp, {"router_aux": aux}


# -- serving ----------------------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens, lengths, cache, *, media=None,
            use_pallas=False, return_logprobs=False):
    """Seed the cache with (right-padded) prompts.

    tokens: (B, S) right-padded; lengths: (B,) true lengths.
    Returns (next_token_logits (B, V), new_cache) —
    or (logits, new_cache, logps (B, S)) when ``return_logprobs`` (the
    behaviour-logprob record for re-prefilled resumed tokens is *not* taken
    from here; behaviour logps are recorded at sampling time).
    """
    B, S = tokens.shape
    seq_mask = (jnp.arange(S)[None, :] < lengths[:, None])
    x, new_cache, _ = backbone(params, cfg, tokens, cache=cache, media=media,
                               seq_mask=seq_mask, lengths=lengths,
                               mode="prefill", use_pallas=use_pallas)
    last = _gather_last(x, lengths)                     # (B, d)
    logits = _logits(params, cfg, last[:, None, :])[:, 0]
    if return_logprobs:
        full = _logits(params, cfg, x)
        lp = token_logprobs_from_logits(full[:, :-1], tokens[:, 1:])
        return logits, new_cache, lp
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, token, cache, cache_len, *,
                media=None, use_pallas=False, paged=None):
    """token: (B,) int32 — the *input* token; returns logits (B, V) for the
    next token plus the updated cache (token's K/V written at cache_len).
    ``paged=(block_table (B, max_pages), page_size)`` decodes against a
    :func:`init_paged_cache` cache."""
    x, new_cache, _ = backbone(params, cfg, token[:, None], cache=cache,
                               cache_len=cache_len, media=media,
                               mode="decode", use_pallas=use_pallas,
                               paged=paged)
    logits = _logits(params, cfg, x)[:, 0]
    return logits, new_cache


def decode_scan(params, cfg: ModelConfig, cache, last_token, cache_len,
                active, aux, *, steps: int, step_fn, media=None,
                use_pallas=False, paged=None):
    """Run ``steps`` fused decode+sample iterations entirely on device.

    One ``jax.lax.scan`` over :func:`decode_step`; the caller supplies the
    sampling / stop policy::

        step_fn(logits, cache_len, active, aux) -> (tok, logp, stop, aux')

    where ``logits (B, V)`` are this step's next-token logits, ``cache_len``
    is the PRE-increment per-slot cache length and ``stop (B,) bool`` marks
    slots that must freeze after consuming ``tok``. Slots with
    ``active == False`` still flow through the batched decode (their state is
    frozen: no cache_len advance, last_token held) — identical to the
    step-wise engine's treatment of idle slots.

    Returns ``((cache, last_token, cache_len, active, aux), ys)`` with
    ``ys = (tokens (steps, B), logps (steps, B), was_active (steps, B))``;
    ``was_active[d]`` is the active mask entering step ``d`` — the host uses
    it to trim post-stop (over-generated) samples.
    """
    def body(carry, _):
        cache, last_tok, clen, act, a = carry
        logits, cache = decode_step(params, cfg, last_tok, cache, clen,
                                    media=media, use_pallas=use_pallas,
                                    paged=paged)
        tok, logp, stop, a = step_fn(logits, clen, act, a)
        clen = clen + act.astype(clen.dtype)
        last_tok = jnp.where(act, tok.astype(last_tok.dtype), last_tok)
        ys = (tok, logp, act)
        act = jnp.logical_and(act, jnp.logical_not(stop))
        return (cache, last_tok, clen, act, a), ys

    return jax.lax.scan(body, (cache, last_token, cache_len, active, aux),
                        None, length=steps)
