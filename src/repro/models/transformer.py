"""Unified decoder over heterogeneous block kinds.

Layers are laid out as ``prefix_pattern`` (unrolled) followed by
``num_repeats`` repeats of ``block_pattern`` executed under ``lax.scan`` with
stacked parameters — compile time is O(pattern), not O(depth), which is what
makes 100-layer × 512-device dry-runs tractable, and ``jax.checkpoint``
(remat) wraps the scan body for training.

Block kinds: attn / local / global / moe / rwkv / hymba / xattn (see
repro.common.config.VALID_BLOCK_KINDS).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, init_mlp, rms_norm, split_keys


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str, dtype):
    d = cfg.d_model
    ks = split_keys(key, 4)
    p = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype)}
    if kind in ("attn", "local", "global"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, dtype)
    elif kind == "moe":
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    elif kind == "xattn":
        p["xattn"] = attn_mod.init_attention(ks[0], cfg, dtype, cross=True)
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, dtype)
        p["mlp_gate"] = jnp.zeros((), dtype)
    elif kind == "rwkv":
        p.update(rwkv_mod.init_rwkv_block(ks[0], cfg, dtype))
        p.pop("ln2", None)
        p["ln2"] = jnp.ones((d,), dtype)
    elif kind == "hymba":
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg, dtype)
        p["fuse_norm_a"] = jnp.ones((d,), dtype)
        p["fuse_norm_s"] = jnp.ones((d,), dtype)
        p["beta"] = jnp.ones((2,), dtype) * 0.5
        p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, dtype)
    else:
        raise ValueError(kind)
    return p


# ---------------------------------------------------------------------------
# per-layer cache init
# ---------------------------------------------------------------------------


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype, *, kv_pages=None):
    """Dense attention K/V leaves (dict keys "k"/"v") are (batch, max_len,
    kv*hd): the heads merged into one minor dim, which the decode kernel
    reads in place. ``kv_pages=(num_pages, page_size)`` switches them to a
    physical page pool (num_pages, page_size, kv, hd) shared by all slots;
    every other leaf keeps its per-slot batch axis (no length axis to
    page)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    if kv_pages is not None:
        kv_shape = (*kv_pages, kv, hd)
    else:
        kv_shape = (batch, max_len, kv * hd)
    if kind in ("attn", "local", "global", "moe"):
        # sliding-window layers only ever read the last `window` entries but
        # we keep the full ring for simplicity of absolute indexing.
        return {"k": jnp.zeros(kv_shape, dtype),
                "v": jnp.zeros(kv_shape, dtype)}
    if kind == "xattn":
        # media K/V are static per request: computed at prefill, reused at
        # every decode step (hillclimb C)
        M = cfg.cross_attn.num_media_tokens
        return {"mk": jnp.zeros((batch, M, kv, hd), dtype),
                "mv": jnp.zeros((batch, M, kv, hd), dtype)}
    if kind == "rwkv":
        return rwkv_mod.init_rwkv_state(cfg, batch, dtype)
    if kind == "hymba":
        di = ssm_mod.d_inner_of(cfg)
        K = cfg.ssm.conv_dim
        return {"k": jnp.zeros(kv_shape, dtype),
                "v": jnp.zeros(kv_shape, dtype),
                "ssm": jnp.zeros((batch, di, cfg.ssm.state_dim), jnp.float32),
                "conv": jnp.zeros((batch, K - 1, di), dtype)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-layer apply
# ---------------------------------------------------------------------------


def _seed_kv(cache, k, v):
    """Prefill write: k/v (B, S, KV, hd) into the first S positions of the
    dense (B, L, KV*hd) cache leaves."""
    B, S = k.shape[:2]

    def put(c, t):
        return jax.lax.dynamic_update_slice(
            c, t.reshape(B, S, -1).astype(c.dtype), (0, 0, 0))
    return put(cache["k"], k), put(cache["v"], v)


def _gather_last(x, lengths):
    """x: (B, S, d), lengths: (B,) -> (B, d) = x[b, lengths[b]-1]."""
    idx = jnp.maximum(lengths - 1, 0)
    return jax.vmap(lambda xb, i: xb[i])(x, idx)


def apply_block(params, cfg: ModelConfig, kind: str, x, *, positions,
                media=None, cache=None, cache_len=None, seq_mask=None,
                lengths=None, mode: str = "train", use_pallas: bool = False,
                paged=None):
    """Returns (x_out, new_cache, aux).

    mode: "train" (no cache), "prefill" (seed cache; all rows padded to the
    same S, right-padded, per-row true ``lengths``), "decode" (x is (B,1,d),
    ``cache_len`` (B,) tokens already in cache). ``paged=(block_table,
    page_size)`` selects the paged-KV decode path (decode mode only).
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    if kind in ("attn", "local", "global", "moe"):
        h = rms_norm(x, params["ln1"], eps=cfg.rms_eps)
        if mode == "decode":
            a, (kc, vc) = attn_mod.attention_block(
                params["attn"], cfg, h, positions, kind=kind,
                kv_cache=(cache["k"], cache["v"]), cache_len=cache_len,
                use_pallas=use_pallas, paged=paged)
            new_cache = dict(cache, k=kc, v=vc)
        else:
            a, (k, v) = attn_mod.attention_block(
                params["attn"], cfg, h, positions, kind=kind,
                use_pallas=use_pallas)
            if mode == "prefill":
                kc, vc = _seed_kv(cache, k, v)
                new_cache = dict(cache, k=kc, v=vc)
        x = x + a
        h2 = rms_norm(x, params["ln2"], eps=cfg.rms_eps)
        if kind == "moe":
            # decode (S==1) uses the dense (dropless) dispatcher — capacity
            # truncation at token-count 1 would drop whole tokens and break
            # decode/full-forward consistency
            if cfg.moe.dispatch == "dense" or h2.shape[1] == 1:
                f, aux = moe_mod.apply_moe(params["moe"], cfg, h2)
            elif cfg.moe.dispatch == "shardmap" and mode == "train":
                # shard_map all-to-all wins for TRAIN (5x memory term vs the
                # auto-SPMD scatter); the 1M-token prefills measured better
                # on the chunked scatter, so non-train modes fall through
                # (EXPERIMENTS.md §Perf D4)
                from repro.common.partitioning import get_activation_mesh
                from repro.models.moe_shardmap import apply_moe_shardmap
                mesh = get_activation_mesh()
                if mesh is not None and "model" in mesh.axis_names:
                    f, aux = apply_moe_shardmap(params["moe"], cfg, h2, mesh)
                else:                       # CPU / no-mesh fallback
                    f, aux = moe_mod.apply_moe_sparse(params["moe"], cfg, h2)
            else:
                f, aux = moe_mod.apply_moe_sparse(params["moe"], cfg, h2)
        else:
            f = apply_mlp(params["mlp"], h2)
        return x + f, new_cache, aux

    if kind == "xattn":
        h = rms_norm(x, params["ln1"], eps=cfg.rms_eps)
        media_kv = None
        if mode == "decode" and cache is not None:
            media_kv = (cache["mk"], cache["mv"])
        a, (mk, mv) = attn_mod.cross_attention_block(
            params["xattn"], cfg, h, media, media_kv=media_kv,
            use_pallas=use_pallas)
        if mode == "prefill" and cache is not None:
            new_cache = dict(cache, mk=mk.astype(cache["mk"].dtype),
                             mv=mv.astype(cache["mv"].dtype))
        x = x + a
        h2 = rms_norm(x, params["ln2"], eps=cfg.rms_eps)
        f = apply_mlp(params["mlp"], h2)
        return (x + jnp.tanh(params["mlp_gate"].astype(x.dtype)) * f,
                new_cache, aux)

    if kind == "rwkv":
        # NOTE: pinning the residual stream to (dp, None, None) here was
        # tried for the per-layer activation re-gathers visible in the rwkv
        # train_4k HLO and REFUTED: collective -21% but memory +68%
        # (EXPERIMENTS.md §Perf E) — XLA's drifting layout is the cheaper
        # global solution.
        st = cache if cache is not None else rwkv_mod.init_rwkv_state(
            cfg, x.shape[0], x.dtype)
        h = rms_norm(x, params["ln1"], eps=cfg.rms_eps)
        y, tm_prev, wkv = rwkv_mod.apply_time_mix(
            params["tm"], cfg, h, st["tm_prev"], st["wkv"],
            seq_mask=seq_mask, use_pallas=use_pallas)
        if lengths is not None:
            tm_prev = _gather_last(h, lengths)
        x = x + y
        h2 = rms_norm(x, params["ln2"], eps=cfg.rms_eps)
        y2, cm_prev = rwkv_mod.apply_channel_mix(params["cm"], cfg, h2, st["cm_prev"])
        if lengths is not None:
            cm_prev = _gather_last(h2, lengths)
        new_cache = {"wkv": wkv, "tm_prev": tm_prev, "cm_prev": cm_prev}
        if mode == "train":
            new_cache = None
        return x + y2, new_cache, aux

    if kind == "hymba":
        h = rms_norm(x, params["ln1"], eps=cfg.rms_eps)
        if mode == "decode":
            a, (kc, vc) = attn_mod.attention_block(
                params["attn"], cfg, h, positions, kind="local",
                kv_cache=(cache["k"], cache["v"]), cache_len=cache_len,
                use_pallas=use_pallas, paged=paged)
            s, ssm_st, conv_st = ssm_mod.apply_ssm(
                params["ssm"], cfg, h, cache["ssm"], cache["conv"],
                use_pallas=use_pallas)
            new_cache = dict(cache, k=kc, v=vc, ssm=ssm_st, conv=conv_st)
        else:
            a, (k, v) = attn_mod.attention_block(
                params["attn"], cfg, h, positions, kind="local",
                use_pallas=use_pallas)
            s, ssm_st, conv_st = ssm_mod.apply_ssm(
                params["ssm"], cfg, h, None, None, seq_mask=seq_mask,
                lengths=lengths, use_pallas=use_pallas)
            if mode == "prefill":
                kc, vc = _seed_kv(cache, k, v)
                new_cache = dict(cache, k=kc, v=vc, ssm=ssm_st, conv=conv_st)
        fused = (params["beta"].astype(x.dtype)[0]
                 * rms_norm(a, params["fuse_norm_a"], eps=cfg.rms_eps)
                 + params["beta"].astype(x.dtype)[1]
                 * rms_norm(s, params["fuse_norm_s"], eps=cfg.rms_eps))
        x = x + fused
        h2 = rms_norm(x, params["ln2"], eps=cfg.rms_eps)
        if mode == "train":
            new_cache = None
        return x + apply_mlp(params["mlp"], h2), new_cache, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack init / apply (prefix unrolled + scanned repeats)
# ---------------------------------------------------------------------------


def init_stack(key, cfg: ModelConfig, dtype):
    kp, kb = jax.random.split(key)
    prefix = []
    for i, kind in enumerate(cfg.prefix_pattern):
        prefix.append(init_block(jax.random.fold_in(kp, i), cfg, kind, dtype))

    R = cfg.num_repeats

    def init_repeat(k):
        ks = split_keys(k, len(cfg.block_pattern))
        return tuple(init_block(ks[j], cfg, kind, dtype)
                     for j, kind in enumerate(cfg.block_pattern))

    body = jax.vmap(init_repeat)(jax.random.split(kb, R))
    return {"prefix": prefix, "body": body}


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, *,
                     kv_pages=None):
    prefix = [init_block_cache(cfg, kind, batch, max_len, dtype,
                               kv_pages=kv_pages)
              for kind in cfg.prefix_pattern]
    one = tuple(init_block_cache(cfg, kind, batch, max_len, dtype,
                                 kv_pages=kv_pages)
                for kind in cfg.block_pattern)
    R = cfg.num_repeats
    body = jax.tree.map(lambda a: jnp.broadcast_to(a, (R,) + a.shape).copy(), one)
    return {"prefix": prefix, "body": body}


def apply_stack(params, cfg: ModelConfig, x, *, positions, media=None,
                cache=None, cache_len=None, seq_mask=None, lengths=None,
                mode: str = "train", use_pallas: bool = False,
                remat: bool = False, paged=None):
    """Run all layers. Returns (x, new_cache, aux_sum)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix = []
    for i, kind in enumerate(cfg.prefix_pattern):
        c = cache["prefix"][i] if cache is not None else None
        x, nc, aux = apply_block(params["prefix"][i], cfg, kind, x,
                                 positions=positions, media=media, cache=c,
                                 cache_len=cache_len, seq_mask=seq_mask,
                                 lengths=lengths, mode=mode,
                                 use_pallas=use_pallas, paged=paged)
        new_prefix.append(nc)
        aux_total = aux_total + aux

    def repeat_body(x, inp):
        p_rep, c_rep = inp
        aux_sum = jnp.zeros((), jnp.float32)
        new_c = []
        for j, kind in enumerate(cfg.block_pattern):
            c = c_rep[j] if c_rep is not None else None
            # ``paged`` (the block table) is a loop-invariant of the layer
            # scan: per-layer page pools are scanned, the table is shared
            x, nc, aux = apply_block(p_rep[j], cfg, kind, x,
                                     positions=positions, media=media,
                                     cache=c, cache_len=cache_len,
                                     seq_mask=seq_mask, lengths=lengths,
                                     mode=mode, use_pallas=use_pallas,
                                     paged=paged)
            new_c.append(nc)
            aux_sum = aux_sum + aux
        if mode == "train":
            return x, aux_sum
        return x, (tuple(new_c), aux_sum)

    body_fn = jax.checkpoint(repeat_body) if remat else repeat_body
    if cache is not None:
        xs = (params["body"], cache["body"])
        x, (new_body, auxs) = jax.lax.scan(body_fn, x, xs)
        new_cache = {"prefix": new_prefix, "body": new_body}
    else:
        xs = (params["body"], None)
        x, auxs = jax.lax.scan(lambda c, i: body_fn(c, (i, None)), x, params["body"])
        new_cache = None
    return x, new_cache, aux_total + jnp.sum(auxs)
