"""Fused IS+GRPO loss Pallas TPU kernels.

CoPRIS's hot train-step math — the "Cal logprob" recompute plus the
clipped cross-stage IS/GRPO objective — runs here as ONE vocab-blocked
pass over the (rows, V) logits: each block contributes to a running
(max, sumexp, target-logit, logit-weighted-sumexp) quadruple per row, and
the final vocab block computes logp, entropy and the full per-token
objective (``grpo.per_token_objective`` — the same function the unfused
path calls, so there is a single source of truth for the RL math). The
(rows, V) logits never touch HBM.

The backward pass recomputes per-block softmax statistics from the saved
O(rows) residuals (lse, E[logit], per-row cotangent coefficients) in two
kernels:

* ``_bwd_dh_kernel`` — grid (row blocks parallel, vocab sequential),
  accumulating dl @ w_blockᵀ into a (block_rows, d) scratch;
* ``_bwd_dw_kernel`` — grid (vocab blocks parallel, rows sequential),
  accumulating h_blockᵀ @ dl into a (d, block_v) scratch.

Two kernels because a single grid cannot accumulate both outputs without
revisiting an output block across its parallel axis. dlogits for block
(r, v) is ``a·(onehot − p) − e·p·(logit − E[logit])`` (times the softcap
chain rule), where ``a``/``e`` are the per-row cotangents of the logp and
entropy channels — O(rows) values the wrapper computes by running
``jax.vjp`` over the elementwise epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import grpo

NEG_INF = -1e30


def _block_logits(h_ref, w_ref, *, softcap):
    h = h_ref[...].astype(jnp.float32)                     # (br, d)
    w = w_ref[...].astype(jnp.float32)                     # (d, bv)
    logits = jax.lax.dot(h, w, preferred_element_type=jnp.float32)
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def _fwd_kernel(t_ref, b_ref, a_ref, h_ref, w_ref,
                loss_ref, ratio_ref, logp_ref, lse_ref, ent_ref,
                m_scr, l_scr, g_scr, u_scr, *,
                block_v, V, softcap, num_v_blocks,
                clip_low, clip_high, use_is, is_ratio_cap, entropy_coef):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)
        u_scr[...] = jnp.zeros_like(u_scr)

    logits = _block_logits(h_ref, w_ref, softcap=softcap)
    ids = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(ids < V, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p_blk = jnp.exp(logits - m_new)                         # unnormalised
    l_scr[...] = l_scr[...] * corr + p_blk.sum(axis=1, keepdims=True)
    # logit-weighted sumexp -> E[logit] -> entropy, one extra FMA per lane
    u_scr[...] = (u_scr[...] * corr
                  + (p_blk * logits).sum(axis=1, keepdims=True))
    m_scr[...] = m_new
    hit = ids == t_ref[...]                                 # (br, bv) vs (br, 1)
    g_scr[...] += jnp.where(hit, logits, 0.0).sum(axis=1, keepdims=True)

    @pl.when(vi == num_v_blocks - 1)
    def _finish():
        lse = m_scr[...] + jnp.log(l_scr[...])
        logp = g_scr[...] - lse
        ebar = u_scr[...] / l_scr[...]                      # E_p[logit]
        ent = lse - ebar
        loss_tok, ratio = grpo.per_token_objective(
            logp, b_ref[...], a_ref[...],
            clip_low=clip_low, clip_high=clip_high, use_is=use_is,
            is_ratio_cap=is_ratio_cap, entropy=ent, entropy_coef=entropy_coef)
        loss_ref[...] = loss_tok
        ratio_ref[...] = ratio
        logp_ref[...] = logp
        lse_ref[...] = lse
        ent_ref[...] = ent


def _block_dlogits(t_ref, h_ref, w_ref, lse_ref, eb_ref, a_ref, e_ref,
                   ids, *, V, softcap):
    """Recompute this block's logits and form dlogits (br, bv)."""
    logits = _block_logits(h_ref, w_ref, softcap=softcap)
    valid = ids < V
    p = jnp.where(valid, jnp.exp(logits - lse_ref[...]), 0.0)
    hit = (ids == t_ref[...]).astype(jnp.float32)
    dl = (a_ref[...] * (hit - p)
          - e_ref[...] * p * (logits - eb_ref[...]))
    if softcap > 0.0:
        dl = dl * (1.0 - jnp.square(logits / softcap))
    return jnp.where(valid, dl, 0.0)


def _bwd_dh_kernel(t_ref, h_ref, w_ref, lse_ref, eb_ref, a_ref, e_ref,
                   dh_ref, acc_scr, *, block_v, V, softcap, num_v_blocks):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ids = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (t_ref.shape[0], block_v), 1)
    dl = _block_dlogits(t_ref, h_ref, w_ref, lse_ref, eb_ref, a_ref, e_ref,
                        ids, V=V, softcap=softcap)
    w = w_ref[...].astype(jnp.float32)
    acc_scr[...] += jax.lax.dot_general(
        dl, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(vi == num_v_blocks - 1)
    def _finish():
        dh_ref[...] = acc_scr[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(t_ref, h_ref, w_ref, lse_ref, eb_ref, a_ref, e_ref,
                   dw_ref, acc_scr, *, block_v, V, softcap, num_r_blocks):
    ri = pl.program_id(1)
    vi = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ids = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (t_ref.shape[0], block_v), 1)
    dl = _block_dlogits(t_ref, h_ref, w_ref, lse_ref, eb_ref, a_ref, e_ref,
                        ids, V=V, softcap=softcap)
    h = h_ref[...].astype(jnp.float32)
    acc_scr[...] += jax.lax.dot_general(
        h, dl, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ri == num_r_blocks - 1)
    def _finish():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


# The dw kernel holds a (d, block_v) f32 accumulator beside double-buffered
# (d, block_v) weight and output blocks: about 20 MiB at d=2048, over the
# 16 MiB default scoped VMEM (v5e has 128 MiB per core).
_BWD_VMEM_LIMIT = 64 * 1024 * 1024


def _fit_block_v(V, block_v):
    """The largest multiple of 128 that is <= ``block_v`` and divides V, so
    the (d, V) weight is blocked in place rather than copied into a padded
    buffer (1 GiB at d=2048, V=128256 in f32); ``block_v`` if none does."""
    for b in range(block_v - block_v % 128, 127, -128):
        if V % b == 0:
            return b
    return block_v


def _pad_rows(hidden, w, targets, extras, block_rows, block_v):
    R, d = hidden.shape
    V = w.shape[1]
    block_rows = min(block_rows, max(R, 8))
    block_v = _fit_block_v(V, block_v) if V > block_v else max(V, 128)
    pR = (-R) % block_rows
    pV = (-V) % block_v
    hp = jnp.pad(hidden, ((0, pR), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, pV)))
    tp = jnp.pad(targets, (0, pR))[:, None].astype(jnp.int32)
    ex = [jnp.pad(x, (0, pR))[:, None].astype(jnp.float32) for x in extras]
    return hp, wp, tp, ex, block_rows, block_v


def fused_is_grpo_fwd_rows(hidden, w, targets, behaviour, adv, *,
                           logit_softcap=0.0, clip_low=0.2, clip_high=0.28,
                           use_is=True, is_ratio_cap=10.0, entropy_coef=0.0,
                           block_rows=256, block_v=512, interpret=True):
    """hidden (R, d); w (d, V); targets (R,) i32; behaviour/adv (R,) f32.

    Returns ``(loss_tok, ratio, logp, lse, entropy)``, each fp32 (R,).
    """
    R, d = hidden.shape
    V = w.shape[1]
    hp, wp, tp, (bp, ap), block_rows, block_v = _pad_rows(
        hidden, w, targets, (behaviour, adv), block_rows, block_v)
    assert hp.shape[0] % block_rows == 0 and wp.shape[1] % block_v == 0
    nr = hp.shape[0] // block_rows
    nv = wp.shape[1] // block_v

    kernel = functools.partial(
        _fwd_kernel, block_v=block_v, V=V, softcap=logit_softcap,
        num_v_blocks=nv, clip_low=clip_low, clip_high=clip_high,
        use_is=use_is, is_ratio_cap=is_ratio_cap, entropy_coef=entropy_coef)
    row_spec = pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0))
    out_shape = jax.ShapeDtypeStruct((hp.shape[0], 1), jnp.float32)
    outs = pl.pallas_call(
        kernel,
        grid=(nr, nv),
        in_specs=[
            row_spec, row_spec, row_spec,
            pl.BlockSpec((block_rows, d), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((d, block_v), lambda ri, vi: (0, vi)),
        ],
        out_specs=[row_spec] * 5,
        out_shape=[out_shape] * 5,
        scratch_shapes=[pltpu.VMEM((block_rows, 1), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tp, bp, ap, hp, wp)
    return tuple(o[:R, 0] for o in outs)


def fused_is_grpo_bwd_rows(hidden, w, targets, lse, ebar, a, e, *,
                           logit_softcap=0.0, block_rows=256, block_v=512,
                           interpret=True):
    """Backward: per-row cotangent coefficients -> (dh (R, d), dw (d, V)).

    ``a`` = dL/dlogp per row, ``e`` = dL/dentropy per row, ``ebar`` =
    E_p[logit] = lse - entropy (saved from the forward).
    """
    R, d = hidden.shape
    V = w.shape[1]
    hp, wp, tp, ex, block_rows, block_v = _pad_rows(
        hidden, w, targets, (lse, ebar, a, e), block_rows, block_v)
    lsep, ebp, ap, ep = ex
    assert hp.shape[0] % block_rows == 0 and wp.shape[1] % block_v == 0
    nr = hp.shape[0] // block_rows
    nv = wp.shape[1] // block_v

    row_spec = pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0))
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_v=block_v, V=V,
                          softcap=logit_softcap, num_v_blocks=nv),
        grid=(nr, nv),
        in_specs=[
            row_spec,
            pl.BlockSpec((block_rows, d), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((d, block_v), lambda ri, vi: (0, vi)),
            row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda ri, vi: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((hp.shape[0], d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(tp, hp, wp, lsep, ebp, ap, ep)

    # dw: vocab blocks parallel, rows sequential — the transposed grid, so
    # each (d, block_v) output block is owned by exactly one program.
    row_spec_t = pl.BlockSpec((block_rows, 1), lambda vi, ri: (ri, 0))
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_v=block_v, V=V,
                          softcap=logit_softcap, num_r_blocks=nr),
        grid=(nv, nr),
        in_specs=[
            row_spec_t,
            pl.BlockSpec((block_rows, d), lambda vi, ri: (ri, 0)),
            pl.BlockSpec((d, block_v), lambda vi, ri: (0, vi)),
            row_spec_t, row_spec_t, row_spec_t, row_spec_t,
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda vi, ri: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((d, wp.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, block_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(tp, hp, wp, lsep, ebp, ap, ep)
    return dh[:R].astype(hidden.dtype), dw[:, :V].astype(w.dtype)
