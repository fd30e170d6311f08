"""GQA attention: chunked-causal (flash-style, memory-safe in pure JAX),
decode-against-cache, and cross-attention.

The chunked implementation is the *reference semantics* for the Pallas
``flash_attn`` kernel (kernels/flash_attn); the model calls either through
``repro.kernels.flash_attn.ops.flash_attention`` (TPU) or this pure-jnp path
(CPU / dry-run), selected by ``use_pallas``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, rms_norm, softcap, split_keys

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = split_keys(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if cross:
        p["gate"] = jnp.zeros((), dtype)   # llama-vision tanh gate (zero init)
    return p


# ---------------------------------------------------------------------------
# core chunked attention (flash-style online softmax, pure jnp)
# ---------------------------------------------------------------------------


def _block_mask(q_pos, k_pos, *, causal: bool, window: int):
    """(Bq, Bk) boolean mask. ``window`` <= 0 disables sliding window."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m




def _mask_for(q_pos, k_pos, Sk, *, causal, window):
    mask = (k_pos < Sk)[None, :]                                 # kv padding
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask                                                   # (bq, bk)


def _scores(q_blk, k_blk, mask, *, scale, attn_softcap):
    """q_blk: (B,bq,G,R,hd); k_blk: (B,bk,G,hd) -> capped+masked (B,G,R,bq,bk)
    plus the pre-cap scores (needed by the softcap backward)."""
    s_raw = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
    s = softcap(s_raw, attn_softcap) if attn_softcap > 0.0 else s_raw
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s, s_raw


def _flash_fwd_impl(q, k, v, causal, window, attn_softcap, scale,
                    block_q, block_k, q_offset):
    """Returns (out (B,Sq,H,hd), L logsumexp (B,G,R,Sq_padded))."""
    B, Sq, H, hd = q.shape
    Sk, G = k.shape[1], k.shape[2]
    R = H // G
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    qb = jnp.moveaxis(qp.reshape(B, nq, block_q, G, R, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, nk, block_k, G, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, block_k, G, hd), 1, 0)

    def outer(qi):
        q_blk = qb[qi].astype(jnp.float32)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def inner(carry, inp):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = inp
            k_pos = ki * block_k + jnp.arange(block_k)
            mask = _mask_for(q_pos, k_pos, Sk, causal=causal, window=window)
            s, _ = _scores(q_blk, k_blk.astype(jnp.float32), mask,
                           scale=scale, attn_softcap=attn_softcap)
            m_new = jnp.maximum(m_run, s.max(axis=-1))           # (B,G,R,bq)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bqgrd", p,
                            v_blk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, G, R, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, G, R, block_q), jnp.float32)
        a0 = jnp.zeros((B, block_q, G, R, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        lnorm = jnp.moveaxis(jnp.maximum(l, 1e-30), -1, 1)        # (B,bq,G,R)
        out = acc / lnorm[..., None]
        return out, m + jnp.log(jnp.maximum(l, 1e-30))            # L (B,G,R,bq)

    outs, Ls = jax.lax.map(outer, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * block_q, H, hd)[:, :Sq]
    L = jnp.concatenate(
        [Ls[i] for i in range(1)], axis=-1) if nq == 1 else \
        jnp.concatenate([Ls[i] for i in range(Ls.shape[0])], axis=-1)
    return out.astype(q.dtype), L                                 # L (B,G,R,Sqp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, attn_softcap, scale, block_q, block_k,
           q_offset):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, attn_softcap, scale,
                             block_q, block_k, q_offset)
    return out


def _flash_fwd(q, k, v, causal, window, attn_softcap, scale, block_q,
               block_k, q_offset):
    out, L = _flash_fwd_impl(q, k, v, causal, window, attn_softcap, scale,
                             block_q, block_k, q_offset)
    return out, (q, k, v, out, L)


def _flash_bwd(causal, window, attn_softcap, scale, block_q, block_k,
               q_offset, res, dout):
    """Flash backward: recomputes probability blocks instead of storing the
    (Sq, Sk) stash the autodiff-through-scan version keeps (hillclimb A in
    EXPERIMENTS.md §Perf — that stash was 6.7 GB/layer for hymba train_4k).
    Two passes: q-major for dq, kv-major for dk/dv."""
    q, k, v, out, L = res
    B, Sq, H, hd = q.shape
    Sk, G = k.shape[1], k.shape[2]
    R = H // G
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))).astype(jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))).astype(jnp.float32)
    dop = jnp.pad(dout, ((0, 0), (0, pq), (0, 0), (0, 0))).astype(jnp.float32)
    outp = jnp.pad(out, ((0, 0), (0, pq), (0, 0), (0, 0))).astype(jnp.float32)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k

    # D_i = rowsum(dout * out): (B, Sqp, H) -> grouped (B, G, R, Sqp)
    Dfull = jnp.moveaxis((dop * outp).sum(-1).reshape(
        B, nq * block_q, G, R), 1, -1)                            # (B,G,R,Sqp)

    qb = jnp.moveaxis(qp.reshape(B, nq, block_q, G, R, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, nk, block_k, G, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, block_k, G, hd), 1, 0)
    dob = jnp.moveaxis(dop.reshape(B, nq, block_q, G, R, hd), 1, 0)
    Lb = jnp.moveaxis(L.reshape(B, G, R, nq, block_q), 3, 0)      # (nq,B,G,R,bq)
    Db = jnp.moveaxis(Dfull.reshape(B, G, R, nq, block_q), 3, 0)

    def _p_and_ds(qi, ki, q_blk, k_blk, L_blk, D_blk, do_blk, v_blk):
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)
        k_pos = ki * block_k + jnp.arange(block_k)
        mask = _mask_for(q_pos, k_pos, Sk, causal=causal, window=window)
        s, s_raw = _scores(q_blk, k_blk, mask, scale=scale,
                           attn_softcap=attn_softcap)
        p = jnp.exp(s - L_blk[..., None])                         # (B,G,R,bq,bk)
        p = jnp.where(mask[None, None, None], p, 0.0)
        dp = jnp.einsum("bqgrd,bkgd->bgrqk", do_blk, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D_blk[..., None])
        if attn_softcap > 0.0:
            t = jnp.tanh(s_raw / attn_softcap)
            ds = ds * (1.0 - t * t)
        return p, ds

    # pass 1: dq (q-major)
    def dq_outer(qi):
        q_blk, L_blk, D_blk, do_blk = qb[qi], Lb[qi], Db[qi], dob[qi]

        def inner(dq_acc, inp):
            ki, k_blk, v_blk = inp
            _, ds = _p_and_ds(qi, ki, q_blk, k_blk, L_blk, D_blk, do_blk,
                              v_blk)
            dq_acc += jnp.einsum("bgrqk,bkgd->bqgrd", ds, k_blk,
                                 preferred_element_type=jnp.float32) * scale
            return dq_acc, None

        dq0 = jnp.zeros((B, block_q, G, R, hd), jnp.float32)
        dq, _ = jax.lax.scan(inner, dq0, (jnp.arange(nk), kb, vb))
        return dq

    dq = jax.lax.map(dq_outer, jnp.arange(nq))                    # (nq,B,bq,G,R,hd)
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, nq * block_q, H, hd)[:, :Sq]

    # pass 2: dk, dv (kv-major)
    def dkv_outer(ki):
        k_blk, v_blk = kb[ki], vb[ki]

        def inner(carry, inp):
            dk_acc, dv_acc = carry
            qi, q_blk, L_blk, D_blk, do_blk = inp
            p, ds = _p_and_ds(qi, ki, q_blk, k_blk, L_blk, D_blk, do_blk,
                              v_blk)
            dv_acc += jnp.einsum("bgrqk,bqgrd->bkgd", p, do_blk,
                                 preferred_element_type=jnp.float32)
            dk_acc += jnp.einsum("bgrqk,bqgrd->bkgd", ds, q_blk,
                                 preferred_element_type=jnp.float32) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, block_k, G, hd), jnp.float32)
        (dk, dv), _ = jax.lax.scan(inner, (z, z),
                                   (jnp.arange(nq), qb, Lb, Db, dob))
        return dk, dv

    dks, dvs = jax.lax.map(dkv_outer, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, nk * block_k, G, hd)[:, :Sk]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, nk * block_k, G, hd)[:, :Sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: float = 0.0,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
):
    """Memory-safe attention (flash-style two-level scan, pure jnp) with a
    flash-style custom VJP: the backward RECOMPUTES probability blocks
    instead of letting autodiff stash every (block_q, block_k) score tile
    (see EXPERIMENTS.md §Perf hillclimb A).

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H a multiple of KV (GQA —
    handled grouped, no head repetition is materialised). ``q_offset`` must
    be a static int: full-sequence forward and right-padded prefill both
    start at absolute position 0; per-row offsets only occur in decode,
    which uses :func:`decode_attention`. Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    if scale <= 0.0:
        scale = hd ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(k.shape[1], 8))
    return _flash(q, k, v, causal, window, attn_softcap, scale,
                  block_q, block_k, int(q_offset))


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: int = 0, attn_softcap: float = 0.0,
                     scale: float = 0.0):
    """Single-token decode attention against a cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, L, KV*hd) as the dense cache
    stores them, or (B, L, KV, hd); cache_len: (B,) — number of valid cache
    entries *including* the current token's K/V (the cache is updated before
    calling). Reference semantics for the ``decode_attn`` Pallas kernel.
    """
    B, _, H, hd = q.shape
    L = k_cache.shape[1]
    k_cache = k_cache.reshape(B, L, -1, hd)
    v_cache = v_cache.reshape(B, L, -1, hd)
    KV = k_cache.shape[2]
    rep = H // KV
    if scale <= 0.0:
        scale = hd ** -0.5
    # grouped GQA einsum — materialising repeated KV heads (jnp.repeat)
    # forces SPMD to gather an L-sharded cache to re-shard it over heads
    # (hillclimb B); the grouped form keeps L sharded end-to-end
    qg = q.reshape(B, 1, KV, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale   # (B,G,R,1,L)
    if attn_softcap > 0.0:
        s = softcap(s, attn_softcap)
    pos = jnp.arange(L)[None, :]                                  # (1, L)
    mask = pos < cache_len[:, None]
    if window > 0:
        mask &= pos >= (cache_len[:, None] - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged KV decode (block-table indirection; reference = gather-to-dense)
# ---------------------------------------------------------------------------


def paged_write_kv(pool, new, block_table, page_size: int, cache_len):
    """Write one decode token's K (or V) into a paged pool.

    pool: (NP, ps, KV, hd) physical pages; new: (B, 1, KV, hd);
    block_table: (B, max_pages) int32 with sentinel NP for unmapped pages;
    cache_len: (B,) logical write position. Sentinel pages flat-index out of
    bounds and the scatter DROPS them — dead/padding slots write nowhere, so
    their recycled pages can already belong to a new trajectory. A write at
    cache_len >= max_pages*ps (slot already full) is likewise forced onto
    the sentinel so it drops instead of clamping into the slot's LAST
    physical page and corrupting position (max_pages-1)*ps."""
    NP, ps = pool.shape[0], pool.shape[1]
    B = new.shape[0]
    max_pages = block_table.shape[1]
    pos = cache_len.astype(jnp.int32)
    pg = block_table[jnp.arange(B), jnp.minimum(pos // page_size,
                                                max_pages - 1)]
    pg = jnp.where(pos < max_pages * page_size, pg, NP)
    flat = pg.astype(jnp.int32) * ps + pos % page_size
    flatpool = pool.reshape(NP * ps, *pool.shape[2:])
    flatpool = flatpool.at[flat].set(new[:, 0].astype(pool.dtype), mode="drop")
    return flatpool.reshape(pool.shape)


def paged_gather_kv(pool, block_table, page_size: int):
    """Gather a paged pool back to the dense per-slot layout
    (B, max_pages * ps, KV, hd). Unmapped (sentinel) pages read as zeros;
    every such position is beyond cache_len and therefore masked to NEG_INF
    by :func:`decode_attention`, so the paged decode is *bit-identical* to
    dense decode (same reduction shape, same masked operands). This is the
    reference semantics for the ``paged_decode_attn`` Pallas kernel, which
    streams only the mapped pages instead of materialising this view."""
    NP, ps = pool.shape[0], pool.shape[1]
    pos = jnp.arange(block_table.shape[1] * ps)
    flat = (block_table[:, pos // page_size].astype(jnp.int32) * ps
            + (pos % page_size).astype(jnp.int32))                # (B, L)
    flatpool = pool.reshape(NP * ps, *pool.shape[2:])
    return jnp.take(flatpool, flat, axis=0, mode="fill", fill_value=0)


# ---------------------------------------------------------------------------
# full attention sub-block (proj + rope + attend + out-proj)
# ---------------------------------------------------------------------------


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def attention_block(params, cfg, x, positions, *, kind: str,
                    kv_cache=None, cache_len=None, use_pallas: bool = False,
                    paged=None):
    """Self-attention sub-block.

    Training/prefill: kv_cache is None -> returns (out, (k, v)) where k/v are
    the full-sequence keys/values (for cache seeding).
    Decode: kv_cache=(k_cache, v_cache) pre-allocated (B, L, KV*hd),
    cache_len (B,) = tokens already in cache; x is (B, 1, d). Returns
    (out, (k_cache', v_cache')) with the new token written at cache_len.
    Paged decode: ``paged=(block_table (B, max_pages) int32, page_size)`` and
    kv_cache holds physical page pools (NP, ps, KV, hd) shared by all slots.
    """
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype
    q = _split_heads(x @ params["wq"].astype(dt), h, hd)
    k = _split_heads(x @ params["wk"].astype(dt), kv, hd)
    v = _split_heads(x @ params["wv"].astype(dt), kv, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], eps=cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], eps=cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    window = cfg.sliding_window if kind == "local" else 0
    cap = cfg.attn_softcap

    if kv_cache is None:
        # full-sequence forward always starts at absolute position 0
        if use_pallas:
            from repro.kernels.flash_attn import ops as fa_ops
            out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                         attn_softcap=cap)
        else:
            out = chunked_attention(q, k, v, causal=True, window=window,
                                    attn_softcap=cap, q_offset=0)
        new_kv = (k, v)
    elif paged is not None:
        k_cache, v_cache = kv_cache
        bt, psz = paged
        k_cache = paged_write_kv(k_cache, k, bt, psz, cache_len)
        v_cache = paged_write_kv(v_cache, v, bt, psz, cache_len)
        if use_pallas:
            # Pallas kernel streams only the mapped pages (bytes scale with
            # sum(cache_len)); the gather-to-dense reference below is the
            # interpret/CPU fallback and the bit-identity oracle.
            from repro.kernels.paged_decode_attn import ops as pda_ops
            out = pda_ops.paged_decode_attention(
                q, k_cache, v_cache, bt, psz, cache_len + 1,
                window=window, attn_softcap=cap)
        else:
            out = decode_attention(q, paged_gather_kv(k_cache, bt, psz),
                                   paged_gather_kv(v_cache, bt, psz),
                                   cache_len + 1, window=window,
                                   attn_softcap=cap)
        new_kv = (k_cache, v_cache)
    else:
        k_cache, v_cache = kv_cache
        B = x.shape[0]
        idx = cache_len                                           # (B,)
        # the dense cache keeps (KV, hd) merged: (B, L, KV*hd)
        k_new = k.reshape(B, 1, kv * hd).astype(k_cache.dtype)
        v_new = v.reshape(B, 1, kv * hd).astype(v_cache.dtype)
        if cfg.cache_update == "onehot":
            # select-based write: SPMD-shardable along the cache length dim
            # (dynamic_update_slice with per-row indices makes XLA gather an
            # L-sharded cache every layer — hillclimb B)
            hit = (jnp.arange(k_cache.shape[1])[None, :]
                   == idx[:, None])[..., None]                    # (B,L,1)
            k_cache = jnp.where(hit, k_new, k_cache)
            v_cache = jnp.where(hit, v_new, v_cache)
        else:
            k_cache = jax.vmap(lambda c, t, i: jax.lax.dynamic_update_slice(
                c, t, (i, 0)))(k_cache, k_new, idx)
            v_cache = jax.vmap(lambda c, t, i: jax.lax.dynamic_update_slice(
                c, t, (i, 0)))(v_cache, v_new, idx)
        if use_pallas:
            from repro.kernels.decode_attn import ops as da_ops
            out = da_ops.decode_attention(q, k_cache, v_cache, cache_len + 1,
                                          window=window, attn_softcap=cap)
        else:
            out = decode_attention(q, k_cache, v_cache, cache_len + 1,
                                   window=window, attn_softcap=cap)
        new_kv = (k_cache, v_cache)

    y = out.reshape(*out.shape[:-2], h * hd) @ params["wo"].astype(dt)
    return y, new_kv


def cross_attention_block(params, cfg, x, media, *, media_kv=None,
                          use_pallas: bool = False):
    """Cross-attention to (projected) media embeddings (B, M, d).
    Non-causal; tanh-gated (llama-vision style).

    ``media_kv``: optional precomputed (mk, mv) — the media K/V are static
    per request, so serving computes them once at prefill and caches them
    (recomputing the 1601-token media projection per decoded token was 48%
    of the VLM decode collective+compute budget — hillclimb C). Returns
    (y, (mk, mv))."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype
    q = _split_heads(x @ params["wq"].astype(dt), h, hd)
    if media_kv is None:
        k = _split_heads(media @ params["wk"].astype(dt), kv, hd)
        v = _split_heads(media @ params["wv"].astype(dt), kv, hd)
    else:
        k, v = media_kv
        k = k.astype(dt)
        v = v.astype(dt)
    out = chunked_attention(q, k, v, causal=False, window=0, q_offset=0)
    y = out.reshape(*out.shape[:-2], h * hd) @ params["wo"].astype(dt)
    return jnp.tanh(params["gate"].astype(dt)) * y, (k, v)
