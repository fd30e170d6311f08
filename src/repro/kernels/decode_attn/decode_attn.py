"""Single-token decode attention Pallas TPU kernel.

One query token per sequence attends to a (possibly huge) KV cache. The
grid iterates KV-length blocks sequentially (trailing grid axis) with the
online-softmax state in VMEM scratch; invalid cache slots (>= cache_len) and
out-of-window slots are masked. This is the serving hot loop — for
decode_32k/long_500k the arithmetic intensity is O(1) FLOP/byte, so the
kernel's job is purely to stream the cache through VMEM at full HBM
bandwidth with no wasted bytes.

Layout: q (B, H, hd) — a single token; k/v caches (B, KV, L, hd). GQA heads
are grouped so each kv head's cache block is loaded once per q-head group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, window, attn_softcap, block_l, num_l_blocks, rep):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[pl.program_id(0)]
    l_start = li * block_l
    lo = cache_len - window if window > 0 else 0
    run = l_start < cache_len
    if window > 0:
        run = jnp.logical_and(run, l_start + block_l > lo)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (rep, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bl, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if attn_softcap > 0.0:
            s = jnp.tanh(s / attn_softcap) * attn_softcap   # (rep, bl)
        pos = l_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < cache_len
        if window > 0:
            mask &= pos >= lo
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(li == num_l_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, cache_len, *, window=0,
                            attn_softcap=0.0, scale=0.0, block_l=512,
                            interpret=True):
    """q: (B, H, hd); k/v_cache: (B, KV, L, hd); cache_len: (B,) — number of
    valid entries (including the current token). Returns (B, H, hd)."""
    B, H, hd = q.shape
    KV, L = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    if scale <= 0.0:
        scale = hd ** -0.5
    block_l = min(block_l, max(L, 8))
    pL = (-L) % block_l
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pL), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pL), (0, 0)))
    nl = kp.shape[2] // block_l

    # group q heads by kv head: (B*KV, rep, hd)
    qg = q.reshape(B, KV, rep, hd).reshape(B * KV, rep, hd)
    kg = kp.reshape(B * KV, nl * block_l, hd)
    vg = vp.reshape(B * KV, nl * block_l, hd)
    lens = jnp.repeat(cache_len.astype(jnp.int32), KV)     # (B*KV,)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, attn_softcap=attn_softcap,
        block_l=block_l, num_l_blocks=nl, rep=rep)

    # the per-row lengths ride in SMEM as a scalar-prefetch operand (a
    # (1,)-blocked SMEM input is not a legal TPU block shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, 1, nl),
        in_specs=[
            pl.BlockSpec((1, rep, hd), lambda b, _, li, ln: (b, 0, 0)),
            pl.BlockSpec((1, block_l, hd), lambda b, _, li, ln: (b, li, 0)),
            pl.BlockSpec((1, block_l, hd), lambda b, _, li, ln: (b, li, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, hd), lambda b, _, li, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qg, kg, vg)
    return out.reshape(B, H, hd)
