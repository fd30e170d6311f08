"""Single-token decode attention Pallas TPU kernel.

One query token per sequence attends to a (possibly huge) KV cache. The
grid iterates KV-length blocks sequentially (trailing grid axis) with the
online-softmax state in VMEM scratch; invalid cache slots (>= cache_len) and
out-of-window slots are masked. This is the serving hot loop — for
decode_32k/long_500k the arithmetic intensity is O(1) FLOP/byte, so the
kernel's job is purely to stream the cache through VMEM at full HBM
bandwidth with no wasted bytes.

Layout: q (B, H, hd) — a single token; k/v caches (B, L, KV*hd), the dense
cache as the model stores it. The BlockSpecs read each KV head's columns
where they lie, so no transpose or pad of the cache runs before the kernel;
the last length block may run past L and is masked in the kernel. GQA heads
are grouped so each kv head's cache block is loaded once per q-head group,
and a row's blocks past its last live one are not fetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _heads_per_block(kv: int, hd: int) -> int:
    """KV heads one cache block carries: one where a head fills whole
    128-lane tiles; a 128-lane group where hd divides 128 and such groups
    tile the KV heads; else the whole KV*hd row (legal as a full dim)."""
    if hd % 128 == 0:
        return 1
    if 128 % hd == 0 and kv % (128 // hd) == 0:
        return 128 // hd
    return kv


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, window, attn_softcap, block_l, num_l_blocks, hd, hpb):
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
        # rows of the last block past L hold unspecified bits: rows at or
        # past cache_len are masked in the scores and zeroed in V, so no
        # 0 * NaN reaches the accumulator
        live = (l_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_l, 1), 0)) < cache_len
        for h in range(hpb):
            cols = slice(h * hd, (h + 1) * hd)
            q = q_ref[0, h].astype(jnp.float32)            # (rep, hd)
            k = k_ref[0, :, cols].astype(jnp.float32)      # (bl, hd)
            v = jnp.where(live, v_ref[0, :, cols].astype(jnp.float32), 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if attn_softcap > 0.0:
                s = jnp.tanh(s / attn_softcap) * attn_softcap   # (rep, bl)
            pos = l_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = pos < cache_len
            if window > 0:
                mask &= pos >= lo
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(li == num_l_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, cache_len, *, window=0,
                            attn_softcap=0.0, scale=0.0, block_l=512,
                            interpret=True):
    """q: (B, H, hd); k/v_cache: (B, L, KV*hd); cache_len: (B,) — number of
    valid entries (including the current token). Returns (B, H, hd)."""
    B, H, hd = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2] // hd
    rep = H // KV
    if scale <= 0.0:
        scale = hd ** -0.5
    block_l = min(block_l, L)
    nl = pl.cdiv(L, block_l)
    hpb = _heads_per_block(KV, hd)
    assert KV % hpb == 0, (KV, hpb)
    G = KV // hpb                          # cache blocks across a row

    # group q heads by kv head, hpb kv heads per grid row: (B*G, hpb, rep, hd)
    qg = q.reshape(B * G, hpb, rep, hd)
    lens = jnp.repeat(cache_len.astype(jnp.int32), G)      # (B*G,)

    def kv_map(r, _, li, ln):
        # past a row's last live block the index stays put, so Pallas
        # fetches nothing for the blocks `run` skips
        last = jnp.maximum(ln[r] - 1, 0) // block_l
        return r // G, jnp.minimum(li, last), r % G

    kernel = functools.partial(
        _kernel, scale=scale, window=window, attn_softcap=attn_softcap,
        block_l=block_l, num_l_blocks=nl, hd=hd, hpb=hpb)

    # the per-row lengths ride in SMEM as a scalar-prefetch operand (a
    # (1,)-blocked SMEM input is not a legal TPU block shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * G, 1, nl),
        in_specs=[
            pl.BlockSpec((1, hpb, rep, hd),
                         lambda r, _, li, ln: (r, 0, 0, 0)),
            pl.BlockSpec((1, block_l, hpb * hd), kv_map),
            pl.BlockSpec((1, block_l, hpb * hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hpb, rep, hd),
                               lambda r, _, li, ln: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hpb, rep, 1), jnp.float32),
            pltpu.VMEM((hpb, rep, 1), jnp.float32),
            pltpu.VMEM((hpb, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * G, hpb, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qg, k_cache, v_cache)
    return out.reshape(B, H, hd)
