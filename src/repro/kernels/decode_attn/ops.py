"""Jit'd wrapper for the decode attention kernel (model cache layout)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attn.decode_attn import decode_attention_kernel


@functools.partial(jax.jit, static_argnames=(
    "window", "attn_softcap", "scale", "block_l", "interpret"))
def decode_attention(q, k_cache, v_cache, cache_len, *, window=0,
                     attn_softcap=0.0, scale=0.0, block_l=512,
                     interpret=None):
    """Model layout: q (B, 1, H, hd); caches (B, L, KV*hd) as the dense
    cache stores them, read in place; cache_len (B,).
    Returns (B, 1, H, hd) — drop-in for models.attention.decode_attention."""
    interp = (jax.default_backend() == "cpu") if interpret is None else interpret
    out = decode_attention_kernel(
        q[:, 0], k_cache, v_cache, cache_len, window=window,
        attn_softcap=attn_softcap, scale=scale, block_l=block_l,
        interpret=interp)
    return out[:, None]
