"""Flash attention Pallas TPU kernel (causal / sliding-window / softcap, GQA).

TPU adaptation notes (DESIGN.md §3): the grid's last dimension iterates KV
blocks *sequentially* per (batch·head, q-block) — TPU grids execute the
trailing axis in order, so the online-softmax state (m, l, acc) lives in
VMEM scratch and persists across KV steps. Block shapes are MXU-aligned
(block_q × head_dim and block_k × head_dim tiles, head_dim a multiple of
128 for full MXU utilisation; smaller head dims still work via padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, attn_softcap, block_q, block_k,
            seq_q, seq_k, num_kv_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip fully-masked blocks (causal: kv block strictly after q block;
    # window: kv block entirely before the window)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window > 0:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if attn_softcap > 0.0:
            s = jnp.tanh(s / attn_softcap) * attn_softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, attn_softcap=0.0,
                         scale=0.0, block_q=256, block_k=256,
                         interpret=True):
    """q: (BH, Sq, hd); k, v: (BH, Sk, hd) — heads already expanded/mapped.
    Returns (BH, Sq, hd)."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    if scale <= 0.0:
        scale = hd ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        attn_softcap=attn_softcap, block_q=block_q, block_k=block_k,
        seq_q=Sq, seq_k=Sk, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq]
