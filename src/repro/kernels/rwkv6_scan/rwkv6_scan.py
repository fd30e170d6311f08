"""WKV6 recurrence Pallas TPU kernel (RWKV6 "Finch" data-dependent decay).

    S_t = diag(w_t)·S_{t-1} + k_tᵀ·v_t ;   y_t = r_t·(diag(u)·k_tᵀv_t + S_{t-1})

TPU adaptation: the recurrence is inherently sequential in t, but each
(batch, head) is independent and the per-step state is a (hd, hd) matrix —
ideal VPU shape. The grid runs (B·H) in parallel and time-chunks
sequentially (trailing grid axis); the state matrix persists in VMEM
scratch across chunks, so HBM traffic per chunk is just the (chunk, hd)
r/k/v/w slices — the O(hd²) state never leaves VMEM until the final-state
write. A GPU port would assign warps per head; here the whole head's state
update is one VPU-vectorised outer product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref,
            s_scr, *, chunk, num_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                       # (1, hd)

    def step(t, state):
        r = r_ref[0, t].astype(jnp.float32)[None, :]       # (1, hd)
        k = k_ref[0, t].astype(jnp.float32)[None, :]
        v = v_ref[0, t].astype(jnp.float32)[None, :]
        w = w_ref[0, t].astype(jnp.float32)[None, :]
        kv = k.T @ v                                       # (hd, hd)
        y = r @ (state + u.T * kv)                         # (1, hd)
        o_ref[0, t] = y[0].astype(o_ref.dtype)
        return w.T * state + kv

    s_scr[...] = jax.lax.fori_loop(0, chunk, step, s_scr[...])

    @pl.when(ci == num_chunks - 1)
    def _finish():
        sf_ref[0] = s_scr[...].astype(sf_ref.dtype)


def wkv6_bh(r, k, v, w, u, s0, *, chunk=128, interpret=True):
    """r,k,v,w: (BH, T, hd); u: (BH, 1, hd); s0: (BH, hd, hd) initial state.
    Returns (y (BH, T, hd), final_state (BH, hd, hd))."""
    BH, T, hd = r.shape
    chunk = min(chunk, max(T, 8))
    pT = (-T) % chunk
    pad = lambda a: jnp.pad(a, ((0, 0), (0, pT), (0, 0)))
    rp, kp, vp, wp = pad(r), pad(k), pad(v), pad(w)
    # pads: k=0 and w=1 keep the state frozen across the tail
    if pT:
        wp = wp.at[:, T:].set(1.0)
        kp = kp.at[:, T:].set(0.0)
    nc = rp.shape[1] // chunk

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    y, sf = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, ci: (b, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, ci: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rp.shape, r.dtype),
            jax.ShapeDtypeStruct(s0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rp, kp, vp, wp, u, s0)
    return y[:, :T], sf
