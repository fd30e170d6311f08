"""Selective-scan (Mamba) Pallas TPU kernel for the hymba hybrid block.

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + Δ_t ⊙ (B_t ⊗ x_t) ;  y_t = C_t·h_t + D⊙x_t

TPU adaptation: channels (d_inner) are independent — grid parallelises over
(batch, channel blocks) with time chunks on the sequential trailing axis.
Per-step state is (block_d, N) in VMEM scratch (N=16 → a single lane tile
when block_d is a multiple of 8). The original CUDA kernel leans on warp
shuffles for the intra-warp scan; on TPU the (block_d, N) state update is a
plain VPU elementwise op, so no cross-lane primitives are needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
            y_ref, sf_ref, s_scr, *, chunk, num_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)                     # (bd, N)
    D = d_ref[...].astype(jnp.float32)                     # (1, bd)
    negA = -jnp.exp(A)

    def step(t, state):
        x = x_ref[0, t].astype(jnp.float32)[None, :]       # (1, bd)
        dt = dt_ref[0, t].astype(jnp.float32)[None, :]     # (1, bd)
        Bc = b_ref[0, t].astype(jnp.float32)[None, :]      # (1, N)
        Cc = c_ref[0, t].astype(jnp.float32)[None, :]      # (1, N)
        dA = jnp.exp(negA * dt.T)                          # (bd, N)
        state = dA * state + (dt * x).T * Bc               # (bd, N)
        y = (state @ Cc.T).T + D * x                       # (1, bd)
        y_ref[0, t] = y[0].astype(y_ref.dtype)
        return state

    s_scr[...] = jax.lax.fori_loop(0, chunk, step, s_scr[...])

    @pl.when(ci == num_chunks - 1)
    def _finish():
        sf_ref[0] = s_scr[...].astype(sf_ref.dtype)


def selective_scan_kernel(x, dt, A, Bc, Cc, D, s0, *, block_d=256,
                          chunk=128, interpret=True):
    """x, dt: (B, T, di); A: (di, N); Bc, Cc: (B, T, N); D: (di,);
    s0: (B, di, N). Returns (y (B, T, di), final_state (B, di, N))."""
    B, T, di = x.shape
    N = A.shape[1]
    block_d = min(block_d, di)
    assert di % block_d == 0, (di, block_d)
    nd = di // block_d
    chunk = min(chunk, max(T, 8))
    pT = (-T) % chunk
    pad3 = lambda a: jnp.pad(a, ((0, 0), (0, pT), (0, 0)))
    xp, dtp, bp, cp = pad3(x), pad3(dt), pad3(Bc), pad3(Cc)
    # dt=0 on pads -> dA=1, dBx=0: state frozen
    nc = xp.shape[1] // chunk
    D2 = D[None, :]                                        # (1, di)

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    y, sf = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, di_, ci: (b, ci, di_)),
            pl.BlockSpec((1, chunk, block_d), lambda b, di_, ci: (b, ci, di_)),
            pl.BlockSpec((block_d, N), lambda b, di_, ci: (di_, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di_, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di_, ci: (b, ci, 0)),
            pl.BlockSpec((1, block_d), lambda b, di_, ci: (0, di_)),
            pl.BlockSpec((1, block_d, N), lambda b, di_, ci: (b, di_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, di_, ci: (b, ci, di_)),
            pl.BlockSpec((1, block_d, N), lambda b, di_, ci: (b, di_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct(s0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, dtp, A, bp, cp, D2, s0.astype(jnp.float32))
    return y[:, :T], sf
