"""Fused vocab-blocked log-prob Pallas TPU kernel.

CoPRIS's cross-stage IS correction recomputes log p(token) under the current
policy for every buffered token (the paper's "Cal logprob" stage — 15–37% of
step time in Table 2). The naive path materialises (rows, V) logits in HBM;
this kernel streams the vocabulary through VMEM in MXU-sized blocks keeping
a running (max, sumexp, target-logit) triple per row — logits never touch
HBM. Grid: (row blocks parallel, vocab blocks sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(t_ref, h_ref, w_ref, o_ref, m_scr, l_scr, g_scr, *,
            block_v, V, softcap, num_v_blocks):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    h = h_ref[...].astype(jnp.float32)                     # (br, d)
    w = w_ref[...].astype(jnp.float32)                     # (d, bv)
    logits = jax.lax.dot(h, w, preferred_element_type=jnp.float32)
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    ids = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(ids < V, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_new)
                  + jnp.exp(logits - m_new).sum(axis=1, keepdims=True))
    m_scr[...] = m_new
    hit = ids == t_ref[...]                                # (br, bv) vs (br, 1)
    g_scr[...] += jnp.where(hit, logits, 0.0).sum(axis=1, keepdims=True)

    @pl.when(vi == num_v_blocks - 1)
    def _finish():
        o_ref[...] = (g_scr[...] - (m_scr[...] + jnp.log(l_scr[...]))
                      ).astype(o_ref.dtype)


def fused_logprob_rows(hidden, w, targets, *, logit_softcap=0.0,
                       block_rows=256, block_v=512, interpret=True):
    """hidden: (R, d); w: (d, V); targets: (R,) int32 -> fp32 (R,)."""
    R, d = hidden.shape
    V = w.shape[1]
    block_rows = min(block_rows, max(R, 8))
    block_v = min(block_v, max(V, 128))
    pR = (-R) % block_rows
    pV = (-V) % block_v
    hp = jnp.pad(hidden, ((0, pR), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, pV)))
    tp = jnp.pad(targets, (0, pR))[:, None].astype(jnp.int32)   # (Rp, 1)
    nr = hp.shape[0] // block_rows
    nv = wp.shape[1] // block_v

    kernel = functools.partial(_kernel, block_v=block_v, V=V,
                               softcap=logit_softcap, num_v_blocks=nv)
    out = pl.pallas_call(
        kernel,
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((block_rows, d), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((d, block_v), lambda ri, vi: (0, vi)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((hp.shape[0], 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tp, hp, wp)
    return out[:R, 0]
