"""Paged single-token decode attention Pallas TPU kernel.

The paged KV backend stores the cache as physical page pools indexed by a
per-sequence block table, so the decode hot loop can no longer stream a
contiguous (B, L, KV*hd) cache: each length block lives at a
runtime-computed page. The block table and sequence lengths are passed as
scalar-prefetch operands (``PrefetchScalarGridSpec``) so the BlockSpec
index maps can compute the page-indexed DMA source *before* the kernel
body runs — the pipeline prefetches exactly the pages each sequence owns,
never the whole pool.

Grid: (B*KV, max_pages); the page axis is sequential ("arbitrary") and
carries the same online-softmax VMEM state as the dense ``decode_attn``
kernel, with one length block == one physical page. Unmapped pages
(block-table sentinel == num_pages) are clamped to a valid page id for the
DMA and their scores masked by logical position >= cache_len, which the
paged allocator guarantees covers every sentinel slot. Per-page work is
skipped entirely (``pl.when``) for pages past the sequence end, so the
streamed bytes scale with sum(cache_len), not B * max_len — the whole
point of paging the cache.

Layout: q (B, H, hd) — one token; k/v pools (NP, KV, ps, hd) with the kv
head MAJOR to the page so one grid step DMAs a single (ps, hd) page block
per kv head (GQA q-head groups share it, as in the dense kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, window, attn_softcap,
            page_size, num_page_blocks, kv):
    g = pl.program_id(0)
    pi = pl.program_id(1)
    b = g // kv

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[b]
    l_start = pi * page_size
    lo = cache_len - window if window > 0 else 0
    run = l_start < cache_len
    if window > 0:
        run = jnp.logical_and(run, l_start + page_size > lo)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if attn_softcap > 0.0:
            s = jnp.tanh(s / attn_softcap) * attn_softcap   # (rep, ps)
        # logical-position mask: covers both the sequence tail inside the
        # final page AND any clamped-sentinel page (whose l_start is then
        # >= cache_len, masking every column)
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

    @pl.when(pi == num_page_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, block_table, cache_len,
                                  *, window=0, attn_softcap=0.0, scale=0.0,
                                  interpret=True):
    """q: (B, H, hd); k/v_pool: (NP, KV, ps, hd); block_table:
    (B, max_pages) int32 with sentinel NP for unmapped pages; cache_len:
    (B,) valid entries including the current token. Returns (B, H, hd)."""
    B, H, hd = q.shape
    NP, KV, ps, _ = k_pool.shape
    max_pages = block_table.shape[1]
    rep = H // KV
    if scale <= 0.0:
        scale = hd ** -0.5

    # group q heads by kv head: (B*KV, rep, hd)
    qg = q.reshape(B, KV, rep, hd).reshape(B * KV, rep, hd)
    bt = block_table.astype(jnp.int32)
    lens = cache_len.astype(jnp.int32)

    def _kv_map(g, pi, bt_ref, len_ref):
        # page-indexed DMA: the block table picks the physical page; the
        # sentinel (NP, unmapped) is clamped in-range — its scores are
        # fully masked by cache_len inside the kernel body
        pg = jnp.minimum(bt_ref[g // KV, pi], NP - 1)
        return (pg, g % KV, 0, 0)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, attn_softcap=attn_softcap,
        page_size=ps, num_page_blocks=max_pages, kv=KV)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # block_table, cache_len
        grid=(B * KV, max_pages),
        in_specs=[
            pl.BlockSpec((1, rep, hd), lambda g, pi, bt, ln: (g, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), _kv_map),
            pl.BlockSpec((1, 1, ps, hd), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, rep, hd), lambda g, pi, bt, ln: (g, 0, 0)),
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
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt, lens, qg, k_pool, v_pool)
    return out.reshape(B, H, hd)
