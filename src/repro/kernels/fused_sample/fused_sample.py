"""Fused on-device top-k/top-p sampling Pallas TPU kernel.

One kernel replaces the decode sampler's full-vocab materialize + sort:
for each row it streams the (rows, V) logits in vocab blocks through a
small number of sequential phases and emits (token, behaviour logp)
without ever holding a full-vocab softmax or sorted copy in HBM:

* ``stats``  — one pass for the global max (softmax reference point);
* ``topk``   — 4 radix passes (8 bits/level over the order-isomorphic
  sortable-uint32 encoding of fp32) that count elements per bin and
  descend to the exact k-th largest VALUE — integer counts, so the
  threshold is bit-exact vs ``jax.lax.top_k`` (ties kept, like the
  reference's ``logits >= thresh`` mask);
* ``topp``   — 4 radix passes accumulating unnormalised softmax MASS
  ``exp(l - m)`` per bin over the top-k survivors, descending to the
  smallest value whose strictly-above mass is < p·Z (same kept set as the
  reference's sort+cumsum up to fp summation order at the boundary);
* ``draw``   — one pass that regenerates jax's exact Gumbel noise
  in-kernel (threefry2x32 counter PRNG + the bit-precise uniform→Gumbel
  transform of ``jax.random.categorical``) and takes a running masked
  argmax of ``l + g``, plus the kept-set logsumexp for the behaviour logp.

Because the Gumbel bits are reconstructed from the SAME per-trajectory
counter streams (``keys`` = raw (B, 2) uint32 threefry keys, exactly what
``rollout._fold_slot_keys`` produces), the sampled token stream is
bit-identical to ``sampler.sample_rows`` — the engine's chunked-decode
invariance (PR 1) survives unchanged. The behaviour logp agrees to fp32
summation order (the kernel's blockwise logsumexp associates differently
than XLA's; tokens, which are what determinism pins, are exact).

Phase counts are static per config: 2 (no truncation), 6 (top-k or
top-p), 10 (both). Grid: (row blocks parallel, phases+vocab sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_TINY = np.float32(np.finfo(np.float32).tiny)
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def _u32(x):
    return jnp.uint32(x)


def _threefry2x32(k0, k1, x0, x1):
    """jax's threefry2x32 (20-round ARX), elementwise over uint32 arrays."""
    ks2 = k0 ^ k1 ^ _u32(0x1BD11BDA)
    ks = (k0, k1, ks2)
    x0 = x0 + k0
    x1 = x1 + k1
    for i in range(5):
        for r in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = ((x1 << _u32(r)) | (x1 >> _u32(32 - r))) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + _u32(i + 1)
    return x0, x1


def _gumbel_bits(gid, k0, k1):
    """Reconstruct jax.random's per-index random bits for a length-V draw.

    Under ``jax_threefry_partitionable`` (the default) index j's bits are
    ``y0 ^ y1`` of ``threefry2x32(key, (0, j))``: the counter is the
    element's flat index split into (hi, lo) words, so any vocab block
    regenerates its own bits independently.
    """
    y0, y1 = _threefry2x32(k0, k1, jnp.zeros_like(gid, jnp.uint32),
                           gid.astype(jnp.uint32))
    return y0 ^ y1


def _gumbel_from_bits(bits):
    """Bit-exact jax.random.gumbel: bits -> uniform(tiny, 1) -> -log(-log)."""
    fb = (bits >> _u32(9)) | _u32(0x3F800000)
    f = jax.lax.bitcast_convert_type(fb, jnp.float32) - 1.0
    u = f * (1.0 - _TINY) + _TINY
    u = jnp.maximum(_TINY, u)
    return -jnp.log(-jnp.log(u))


def _sortable(l):
    """fp32 -> order-isomorphic uint32 (larger float <-> larger uint)."""
    s = jax.lax.bitcast_convert_type(l, jnp.uint32)
    return jnp.where(s >> _u32(31) == _u32(1), ~s, s | _u32(0x80000000))


def _unsortable(s):
    u = jnp.where(s >= _u32(0x80000000), s ^ _u32(0x80000000), ~s)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _histogram(byte, weight):
    """byte (br, bv) uint32 in [0,256); weight (br, bv) f32 -> (br, 256)."""
    eq = byte[..., None] == jax.lax.broadcasted_iota(
        jnp.uint32, (byte.shape[0], byte.shape[1], 256), 2)
    return (weight[..., None] * eq.astype(jnp.float32)).sum(axis=1)


def _mass_above(bins):
    """bins (br, 256) -> per-bin total strictly ABOVE that bin, and total.

    An inclusive prefix sum as a matmul with an upper-triangular ones
    matrix (Mosaic has no cumsum); HIGHEST precision keeps it f32-exact
    for the integer counts of the top-k passes."""
    n = bins.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    upper = (row <= col).astype(jnp.float32)
    incl = jax.lax.dot(bins, upper, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    total = bins.sum(axis=1, keepdims=True)
    return total - incl, total


def _first_true(mask):
    """(br, n) bool -> (br, 1) int32 index of the first True per row (0 if
    none): ``jnp.argmax`` on a mask, from reductions Mosaic lowers."""
    idx = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    first = jnp.where(mask, idx, mask.shape[1]).min(axis=1, keepdims=True)
    return jnp.where(first == mask.shape[1], 0, first)


def _pick(x, idx):
    """x (br, n), idx (br, 1) int32 -> x[r, idx[r]] as (br, 1): a masked
    reduction in place of ``take_along_axis``'s gather."""
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col == idx, x, 0).sum(axis=1, keepdims=True)


def _sample_kernel(k0_ref, k1_ref, l_ref,
                   tok_ref, logp_ref,
                   m_scr, bins_scr, pre_scr, rem_scr, am_scr, c_scr, tau_scr,
                   best_scr, bidx_scr, ltok_scr, sum_scr, *,
                   schedule, block_v, V, temperature, top_k, top_p,
                   num_v_blocks):
    ph = pl.program_id(1)
    vi = pl.program_id(2)
    last_v = num_v_blocks - 1

    l = l_ref[...].astype(jnp.float32) / temperature       # (br, bv)
    ids = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, l.shape, 1)
    valid = ids < V

    for p_idx, (kind, lvl) in enumerate(schedule):
        here = ph == p_idx

        if kind == "stats":
            @pl.when(here & (vi == 0))
            def _init_stats():
                m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
                tau_scr[...] = jnp.full_like(tau_scr, -jnp.inf)

            @pl.when(here)
            def _stats():
                blk = jnp.where(valid, l, -jnp.inf).max(axis=1, keepdims=True)
                m_scr[...] = jnp.maximum(m_scr[...], blk)

        elif kind in ("topk", "topp"):
            @pl.when(here & (vi == 0))
            def _init_pass(kind=kind, lvl=lvl):
                bins_scr[...] = jnp.zeros_like(bins_scr)
                if lvl == 0:
                    pre_scr[...] = jnp.zeros_like(pre_scr)
                    if kind == "topk":
                        rem_scr[...] = jnp.full_like(rem_scr, float(top_k))
                    else:
                        am_scr[...] = jnp.zeros_like(am_scr)

            @pl.when(here)
            def _accumulate(kind=kind, lvl=lvl):
                s = _sortable(l)
                match = valid
                if kind == "topp":
                    match = match & (l >= tau_scr[...])
                if lvl > 0:
                    match = match & ((s >> _u32(32 - 8 * lvl)) == pre_scr[...])
                byte = (s >> _u32(24 - 8 * lvl)) & _u32(0xFF)
                if kind == "topk":
                    weight = match.astype(jnp.float32)
                else:
                    weight = jnp.where(match, jnp.exp(l - m_scr[...]), 0.0)
                bins_scr[...] += _histogram(byte, weight)

            @pl.when(here & (vi == last_v))
            def _select(kind=kind, lvl=lvl):
                bins = bins_scr[...]
                above, total = _mass_above(bins)
                if kind == "topk":
                    rem = rem_scr[...]
                    # the k-th largest lives in the unique bin whose
                    # strictly-above count is < k_rem <= inclusive count
                    hitb = (above < rem) & (above + bins >= rem)
                    b = _first_true(hitb)
                    rem_scr[...] = rem - _pick(above, b)
                else:
                    if lvl == 0:
                        c_scr[...] = total * top_p
                    am = am_scr[...]
                    # smallest non-empty bin whose above-mass stays < p*Z
                    ok = (am + above < c_scr[...]) & (bins > 0)
                    b = _first_true(ok)
                    am_scr[...] = am + _pick(above, b)
                pre = (pre_scr[...] << _u32(8)) | b.astype(jnp.uint32)
                pre_scr[...] = pre
                if lvl == 3:
                    tau_scr[...] = jnp.maximum(tau_scr[...], _unsortable(pre))

        elif kind == "draw":
            @pl.when(here & (vi == 0))
            def _init_draw():
                best_scr[...] = jnp.full_like(best_scr, -jnp.inf)
                bidx_scr[...] = jnp.zeros_like(bidx_scr)
                ltok_scr[...] = jnp.zeros_like(ltok_scr)
                sum_scr[...] = jnp.zeros_like(sum_scr)

            @pl.when(here)
            def _draw():
                bits = _gumbel_bits(ids, k0_ref[...], k1_ref[...])
                g = _gumbel_from_bits(bits)
                kept = valid & (l >= tau_scr[...])
                val = jnp.where(kept, l + g, NEG_INF)
                bmax = val.max(axis=1, keepdims=True)
                barg = _first_true(val == bmax)
                lsel = _pick(l, barg)
                upd = bmax > best_scr[...]
                best_scr[...] = jnp.where(upd, bmax, best_scr[...])
                bidx_scr[...] = jnp.where(
                    upd, (barg + vi * block_v).astype(jnp.int32), bidx_scr[...])
                ltok_scr[...] = jnp.where(upd, lsel, ltok_scr[...])
                sum_scr[...] += jnp.where(
                    kept, jnp.exp(l - m_scr[...]), 0.0).sum(1, keepdims=True)

            @pl.when(here & (vi == last_v))
            def _emit():
                tok_ref[...] = bidx_scr[...]
                logp_ref[...] = (ltok_scr[...]
                                 - (m_scr[...] + jnp.log(sum_scr[...])))


def fused_sample_rows_kernel(keys, logits, *, temperature, top_k, top_p,
                             block_rows=8, block_v=512, interpret=True):
    """keys (R, 2) uint32; logits (R, V) fp32 -> (tok (R,) i32, logp (R,)).

    temperature must be > 0 (greedy is handled by the ops wrapper).
    top_k <= 0 or >= V disables top-k; top_p >= 1 disables top-p — the
    same static semantics as the XLA reference sampler.
    """
    R, V = logits.shape
    has_topk = 0 < top_k < V
    has_topp = top_p < 1.0
    schedule = [("stats", None)]
    if has_topk:
        schedule += [("topk", lvl) for lvl in range(4)]
    if has_topp:
        schedule += [("topp", lvl) for lvl in range(4)]
    schedule += [("draw", None)]

    block_rows = min(block_rows, max(R, 8))
    block_v = min(block_v, max(V, 128))
    pR = (-R) % block_rows
    pV = (-V) % block_v
    lp = jnp.pad(logits, ((0, pR), (0, pV)))
    kp = jnp.pad(keys.astype(jnp.uint32), ((0, pR), (0, 0)))
    k0, k1 = kp[:, :1], kp[:, 1:2]
    nr = lp.shape[0] // block_rows
    nv = lp.shape[1] // block_v

    kernel = functools.partial(
        _sample_kernel, schedule=tuple(schedule), block_v=block_v, V=V,
        temperature=float(temperature), top_k=int(top_k),
        top_p=float(top_p), num_v_blocks=nv)
    row_spec = pl.BlockSpec((block_rows, 1), lambda ri, ph, vi: (ri, 0))
    scr = lambda shape, dt: pltpu.VMEM(shape, dt)  # noqa: E731
    tok, logp = pl.pallas_call(
        kernel,
        grid=(nr, len(schedule), nv),
        in_specs=[
            row_spec, row_spec,
            pl.BlockSpec((block_rows, block_v), lambda ri, ph, vi: (ri, vi)),
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((lp.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((lp.shape[0], 1), jnp.float32),
        ],
        scratch_shapes=[
            scr((block_rows, 1), jnp.float32),      # m: global max
            scr((block_rows, 256), jnp.float32),    # radix bins
            scr((block_rows, 1), jnp.uint32),       # radix prefix
            scr((block_rows, 1), jnp.float32),      # top-k remaining count
            scr((block_rows, 1), jnp.float32),      # top-p mass above prefix
            scr((block_rows, 1), jnp.float32),      # top-p target mass p*Z
            scr((block_rows, 1), jnp.float32),      # value threshold tau
            scr((block_rows, 1), jnp.float32),      # draw: best l+g
            scr((block_rows, 1), jnp.int32),        # draw: argmax index
            scr((block_rows, 1), jnp.float32),      # draw: l at argmax
            scr((block_rows, 1), jnp.float32),      # draw: kept sumexp
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(k0, k1, lp)
    return tok[:R, 0], logp[:R, 0]
