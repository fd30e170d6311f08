"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

Kernels run in interpret=True mode on CPU (the kernel body executes in
Python) — this validates the block decomposition, masking, and online
accumulators against the reference semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attn import ops as da_ops
from repro.kernels.decode_attn import ref as da_ref
from repro.kernels.flash_attn import ops as fa_ops
from repro.kernels.flash_attn import ref as fa_ref
from repro.kernels.fused_logprob import ops as flp_ops
from repro.kernels.fused_logprob import ref as flp_ref
from repro.kernels.paged_decode_attn import ops as pda_ops
from repro.kernels.paged_decode_attn import ref as pda_ref
from repro.kernels.rwkv6_scan import ops as wkv_ops
from repro.kernels.rwkv6_scan import ref as wkv_ref
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan import ref as ssm_ref

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, Sq, Sk, H, KV, hd, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 100, 100, 4, 4, 32, True, 0, 50.0, jnp.float32),
    (2, 256, 256, 8, 2, 64, True, 64, 0.0, jnp.float32),
    (1, 64, 192, 4, 1, 64, False, 0, 0.0, jnp.float32),
    (1, 128, 128, 2, 2, 128, True, 0, 0.0, jnp.bfloat16),
    (2, 96, 96, 5, 5, 64, True, 32, 0.0, jnp.float32),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_vs_naive(case):
    B, Sq, Sk, H, KV, hd, causal, win, cap, dt = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dt)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd), dt)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd), dt)
    ref = fa_ref.naive_attention(q, k, v, causal=causal, window=win,
                                 attn_softcap=cap)
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=win,
                                 attn_softcap=cap, block_q=64, block_k=64)
    atol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_matches_chunked_model_path():
    """The kernel and the model's chunked-jnp path agree (same semantics)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 70, 4, 64))
    k = jax.random.normal(ks[1], (2, 70, 2, 64))
    v = jax.random.normal(ks[2], (2, 70, 2, 64))
    a = fa_ref.chunked_attention(q, k, v, causal=True, q_offset=0)
    b = fa_ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DA_CASES = [
    (2, 256, 4, 2, 64, 0, 0.0, jnp.float32),
    (3, 200, 8, 8, 32, 0, 30.0, jnp.float32),
    (2, 512, 4, 1, 64, 128, 0.0, jnp.float32),
    (1, 96, 5, 5, 64, 32, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DA_CASES)
def test_decode_attention(case):
    B, L, H, KV, hd, win, cap, dt = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dt)
    kc = jax.random.normal(ks[1], (B, L, KV * hd), dt)
    vc = jax.random.normal(ks[2], (B, L, KV * hd), dt)
    cl = jnp.arange(B) * 37 % (L - 8) + 5
    ref = da_ref.decode_attention(q, kc, vc, cl, window=win, attn_softcap=cap)
    out = da_ops.decode_attention(q, kc, vc, cl, window=win, attn_softcap=cap,
                                  block_l=64)
    atol = 2e-5 if dt == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@given(B=st.integers(1, 3), L=st.integers(16, 160), hd=st.sampled_from([32, 64]),
       win=st.sampled_from([0, 16, 48]))
@settings(max_examples=15, deadline=None)
def test_decode_attention_hypothesis(B, L, hd, win):
    ks = jax.random.split(jax.random.PRNGKey(B * 1000 + L), 3)
    q = jax.random.normal(ks[0], (B, 1, 4, hd))
    kc = jax.random.normal(ks[1], (B, L, 2 * hd))
    vc = jax.random.normal(ks[2], (B, L, 2 * hd))
    cl = (jnp.arange(B) * 13) % (L - 2) + 2
    ref = da_ref.decode_attention(q, kc, vc, cl, window=win)
    out = da_ops.decode_attention(q, kc, vc, cl, window=win, block_l=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def _transposed_padded_decode_attention(q, kc, vc, cl, *, window, cap,
                                        block_l):
    """Oracle: the online-softmax kernel as it ran on a relaid-out copy of
    the cache, (B*KV, L padded to block_l, hd), one grid row per (row, KV
    head) and every length block fetched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, H, hd = q.shape
    L = kc.shape[1]
    KV = kc.shape[2] // hd
    rep, scale = H // KV, hd ** -0.5

    def relayout(c):
        c = c.reshape(B, L, KV, hd).transpose(0, 2, 1, 3)
        c = jnp.pad(c, ((0, 0), (0, 0), (0, (-L) % block_l), (0, 0)))
        return c.reshape(B * KV, -1, hd)

    kg, vg = relayout(kc), relayout(vc)
    nl = kg.shape[1] // block_l

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        li = pl.program_id(2)

        @pl.when(li == 0)
        def _():
            m_scr[...] = jnp.full_like(m_scr, -1e30)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        n = len_ref[pl.program_id(0)]
        start = li * block_l
        lo = n - window if window > 0 else 0
        run = start < n
        if window > 0:
            run = jnp.logical_and(run, start + block_l > lo)

        @pl.when(run)
        def _():
            qf = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if cap > 0.0:
                s = jnp.tanh(s / cap) * cap
            pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = pos < n
            if window > 0:
                mask &= pos >= lo
            s = jnp.where(mask, s, -1e30)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        @pl.when(li == nl - 1)
        def _():
            o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                        ).astype(o_ref.dtype)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B * KV, 1, nl),
        in_specs=[pl.BlockSpec((1, rep, hd), lambda b, _, li, ln: (b, 0, 0)),
                  pl.BlockSpec((1, block_l, hd),
                               lambda b, _, li, ln: (b, li, 0)),
                  pl.BlockSpec((1, block_l, hd),
                               lambda b, _, li, ln: (b, li, 0))],
        out_specs=pl.BlockSpec((1, rep, hd), lambda b, _, li, ln: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, hd), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, rep, hd), q.dtype),
        interpret=True,
    )(jnp.repeat(cl.astype(jnp.int32), KV), q[:, 0].reshape(B * KV, rep, hd),
      kg, vg)
    return out.reshape(B, 1, H, hd)


DA_BIT_CASES = [
    # B, L, H, KV, hd, window, softcap, dtype: L never a multiple of 64
    (3, 200, 12, 2, 128, 0, 0.0, jnp.bfloat16),   # one head per block
    (2, 150, 16, 8, 64, 0, 0.0, jnp.float32),     # 128-lane pairs of heads
    (2, 200, 12, 2, 128, 48, 0.0, jnp.float32),   # window
    (2, 100, 4, 2, 32, 0, 30.0, jnp.float32),     # whole row (tiny)
    (2, 90, 5, 5, 64, 32, 0.0, jnp.bfloat16),     # whole row (hymba)
]


@pytest.mark.parametrize("case", DA_BIT_CASES)
def test_decode_attention_bit_identical_to_relayout(case):
    """Reading the cache in place (ragged last block masked, blocks past
    the last live one not fetched) changes no bit of the output."""
    B, L, H, KV, hd, win, cap, dt = case
    ks = jax.random.split(jax.random.PRNGKey(L + hd), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dt)
    kc = jax.random.normal(ks[1], (B, L, KV * hd), dt)
    vc = jax.random.normal(ks[2], (B, L, KV * hd), dt)
    cl = jnp.array([L, 37, 65, 129][:B], jnp.int32)
    want = _transposed_padded_decode_attention(q, kc, vc, cl, window=win,
                                               cap=cap, block_l=64)
    got = da_ops.decode_attention(q, kc, vc, cl, window=win,
                                  attn_softcap=cap, block_l=64)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _random_block_tables(B, NP, max_pages, ps, cache_len, seed):
    """Block tables with scattered physical pages and sentinel (NP) tails."""
    rng = np.random.default_rng(seed)
    bt = np.full((B, max_pages), NP, np.int32)
    for b in range(B):
        npg = -(-int(cache_len[b]) // ps)
        bt[b, :npg] = rng.choice(NP, npg, replace=False)
    return jnp.asarray(bt)


PDA_CASES = [
    # B, NP, max_pages, ps, H, KV, hd, win, cap, dtype
    (2, 12, 4, 16, 4, 2, 64, 0, 0.0, jnp.float32),
    (3, 20, 6, 8, 8, 8, 32, 0, 30.0, jnp.float32),
    (2, 16, 8, 16, 4, 1, 64, 48, 0.0, jnp.float32),
    (1, 9, 3, 32, 5, 5, 64, 0, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", PDA_CASES)
def test_paged_decode_attention(case):
    B, NP, mp, ps, H, KV, hd, win, cap, dt = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dt)
    kp = jax.random.normal(ks[1], (NP, ps, KV, hd), dt)
    vp = jax.random.normal(ks[2], (NP, ps, KV, hd), dt)
    cl = (jnp.arange(B) * 29) % (mp * ps - 2) + 2
    bt = _random_block_tables(B, NP, mp, ps, cl, seed=B + NP)
    ref = pda_ref.paged_decode_attention(q, kp, vp, bt, ps, cl, window=win,
                                         attn_softcap=cap)
    out = pda_ops.paged_decode_attention(q, kp, vp, bt, ps, cl, window=win,
                                         attn_softcap=cap)
    atol = 3e-5 if dt == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_paged_matches_dense_decode_attention():
    """A contiguous identity block table reduces paged attention to the
    dense kernel's semantics on the same cache bytes."""
    B, mp, ps, H, KV, hd = 2, 4, 16, 4, 2, 64
    L = mp * ps
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    kc = jax.random.normal(ks[1], (B, L, KV, hd))
    vc = jax.random.normal(ks[2], (B, L, KV, hd))
    cl = jnp.array([L - 3, 7])
    # pool = the two caches stacked page-wise; identity-ish block tables
    kp = kc.reshape(B * mp, ps, KV, hd)
    vp = vc.reshape(B * mp, ps, KV, hd)
    bt = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    ref = da_ref.decode_attention(q, kc, vc, cl)
    out = pda_ops.paged_decode_attention(q, kp, vp, bt, ps, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@given(B=st.integers(1, 3), mp=st.integers(1, 5),
       ps=st.sampled_from([8, 16]), extra=st.integers(0, 6))
@settings(max_examples=15, deadline=None)
def test_paged_decode_attention_hypothesis(B, mp, ps, extra):
    NP = B * mp + extra
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + mp * 10 + ps), 3)
    q = jax.random.normal(ks[0], (B, 1, 4, 32))
    kp = jax.random.normal(ks[1], (NP, ps, 2, 32))
    vp = jax.random.normal(ks[2], (NP, ps, 2, 32))
    cl = (jnp.arange(B) * 13) % (mp * ps - 1) + 1
    bt = _random_block_tables(B, NP, mp, ps, cl, seed=extra)
    ref = pda_ref.paged_decode_attention(q, kp, vp, bt, ps, cl)
    out = pda_ops.paged_decode_attention(q, kp, vp, bt, ps, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# rwkv6 wkv scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [(2, 64, 4, 32, 16), (1, 100, 2, 64, 32),
                                  (2, 33, 3, 16, 128)])
def test_wkv6(case):
    B, S, H, hd, chunk = case
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.2
    y_ref, sf_ref = wkv_ref.wkv6_scan(r, k, v, w, u, s0)
    y, sf = wkv_ops.wkv6(r, k, v, w, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf_ref), atol=1e-4)


def test_wkv6_state_streaming():
    """Running two half-sequences with carried state == one full run."""
    B, S, H, hd = 1, 40, 2, 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jnp.zeros((B, H, hd, hd))
    y_full, sf_full = wkv_ops.wkv6(r, k, v, w, u, s0, chunk=8)
    y1, s1 = wkv_ops.wkv6(r[:, :20], k[:, :20], v[:, :20], w[:, :20], u, s0, chunk=8)
    y2, s2 = wkv_ops.wkv6(r[:, 20:], k[:, 20:], v[:, 20:], w[:, 20:], u, s1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sf_full), atol=1e-4)


# ---------------------------------------------------------------------------
# ssm selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [(2, 64, 128, 16, 32), (1, 50, 64, 8, 16),
                                  (2, 33, 256, 16, 128)])
def test_selective_scan(case):
    B, T, di, N, chunk = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, T, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, di))) * 0.1
    A_log = jnp.log(jnp.abs(jax.random.normal(ks[2], (di, N))) + 0.5)
    Bc = jax.random.normal(ks[3], (B, T, N)) * 0.5
    Cc = jax.random.normal(ks[4], (B, T, N)) * 0.5
    D = jax.random.normal(ks[5], (di,)) * 0.2
    s0 = jnp.zeros((B, di, N))
    y_ref, sf_ref = ssm_ref.selective_scan(x, dt, A_log, Bc, Cc, D, s0)
    y, sf = ssm_ops.selective_scan(x, dt, A_log, Bc, Cc, D, s0,
                                   block_d=64, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf_ref), atol=1e-4)


# ---------------------------------------------------------------------------
# fused logprob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [(2, 16, 64, 1000, 0.0), (1, 7, 128, 2048, 30.0),
                                  (3, 5, 32, 517, 0.0)])
def test_fused_logprob(case):
    B, S, d, V, cap = case
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (B, S, d)) * 0.3
    w = jax.random.normal(ks[1], (d, V)) * 0.3
    t = jax.random.randint(ks[2], (B, S), 0, V)
    ref = flp_ref.fused_logprob(h, w, t, logit_softcap=cap)
    blk = flp_ref.fused_logprob(h, w, t, logit_softcap=cap, vocab_block=128)
    pal = flp_ops.fused_logprob(h, w, t, logit_softcap=cap,
                                block_rows=8, block_v=128)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=1e-4)


def test_fused_logprob_is_log_softmax():
    """Oracle cross-check against the direct log_softmax gather."""
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (2, 9, 32)) * 0.5
    w = jax.random.normal(ks[1], (32, 301)) * 0.5
    t = jax.random.randint(ks[2], (2, 9), 0, 301)
    logits = jnp.einsum("bsd,dv->bsv", h, w)
    want = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               t[..., None], -1)[..., 0]
    got = flp_ref.fused_logprob(h, w, t, vocab_block=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
