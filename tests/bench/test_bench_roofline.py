"""FLOP and byte counts on known shapes, and the peaks table. The
``qwen2`` family's counts and the readers built on them give, to the last
bit, what they gave before the counts moved behind the family modules
(the golden numbers below were computed by the earlier ``roofline``
functions on the same inputs)."""
import dataclasses

import numpy as np
import pytest

from bench import roofline, spec

QWEN2 = spec.load_family("qwen2")


def _cfgs():
    bench = spec.load_benchmark()
    return [(c["name"], spec.load_config(bench, c["name"]))
            for c in bench["configs"]]


def _counts(cfg):
    return spec.family(cfg).counts({"cfg": cfg})


def ctx(n):
    """The keys a layer reads at a context of ``n``: at most its window."""
    return lambda window: min(n, window) if window else n


@pytest.mark.parametrize("name,cfg", _cfgs())
def test_forward_flops_match_param_count(name, cfg):
    m = spec.model_config(cfg, name)
    # 2 FLOPs per matrix parameter a token passes through: every layer
    # matrix and the head; norm scales and the embedding lookup do none
    n = m.param_count() - 2 * m.d_model * m.num_layers
    if not m.tie_embeddings:
        n -= m.vocab_size * m.d_model        # the lookup table
    assert _counts(cfg).forward_token_flops(ctx(0.0), logits=True) == 2 * n
    for L in (2, 5):
        c2 = dict(cfg, num_hidden_layers=L)
        m2 = dataclasses.replace(m, num_layers=L)
        grown = (m2.param_count() - m.param_count()
                 - 2 * m.d_model * (L - m.num_layers))
        assert (_counts(c2).forward_token_flops(ctx(0.0), logits=False)
                - _counts(cfg).forward_token_flops(ctx(0.0), logits=False)
                == 2 * grown)


def test_known_shapes():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 2, "num_hidden_layers": 3, "vocab_size": 10}
    m = QWEN2.counts({"cfg": cfg})
    # QK^T and PV: 2 * 2 FLOPs per head dim per key, 4 heads, 3 layers
    assert m.attn_flops(ctx(5)) == 4 * 3 * 4 * 2 * 5
    f, b = m.decode_attn_cost(ctx(100), tokens=10)
    assert f == 4 * 3 * 4 * 2 * 100
    # K and V of 2 heads x 2 dims in bf16 per key, q and out per token
    assert b == 3 * (2 * 2 * 2 * 2 * 100 + 2 * 10 * 4 * 2 * 2)
    f, b = roofline.fused_is_grpo_cost(cfg, rows=7)
    assert f == 6 * 7 * 8 * 10
    assert roofline.causal_ctx_sum(4) == 10
    one = m.train_flops([4], [2])
    assert one == 3 * (2 * 3 * QWEN2.layer_params(cfg) * 4
                       + m.attn_flops(ctx(1)) * 10
                       + 2 * 8 * 10 * 2)


def _r1():
    return spec.load_config(spec.load_benchmark(), "r1-distill-qwen-1.5b")


# (context, logits) -> forward FLOPs of a token, r1-distill-qwen-1.5b
GOLDEN_FWD = {(0.0, False): 187170816.0, (0.0, True): 420544512.0,
              (1.0, False): 187183104.0, (1.0, True): 420556800.0,
              (1234.5, False): 202340352.0, (1234.5, True): 435714048.0,
              (2496, False): 217841664.0, (2496, True): 451215360.0}


def test_qwen2_counts_equal_golden():
    m = QWEN2.counts({"cfg": _r1()})
    for (n, logits), want in GOLDEN_FWD.items():
        assert m.forward_token_flops(ctx(n), logits=logits) == want
    tl = np.array([431, 2496, 1702, 655], np.int32)
    rl = np.array([0, 2048, 1270, 215], np.float32)
    assert m.train_flops(tl, rl) == 5620214255616.0
    assert m.causal_attn_flops(424.75) == 1111064448.0
    assert m.decode_attn_cost(ctx(2.5e6), 1984) == (30720000000.0, 5144379392.0)


def test_windowed_layer_reads_min_of_keys_and_window():
    full = roofline.Layers(n=2, window=0, heads=4, kv_heads=2, head_dim=8,
                           params=1000)
    win = dataclasses.replace(full, window=100)
    F, W = roofline.Stack([full], 50), roofline.Stack([win], 50)
    per_key = 4.0 * 2 * 4 * 8
    assert W.attn_flops(ctx(60)) == F.attn_flops(ctx(60)) == per_key * 60
    assert W.attn_flops(ctx(300)) == per_key * 100
    assert W.forward_token_flops(ctx(300), logits=True) == \
        F.forward_token_flops(ctx(100), logits=True)
    # a causal sequence of 300: keys 1..100, then 100 for each of 200
    assert W.causal_attn_flops(300) == per_key * (5050 + 200 * 100)
    assert F.causal_attn_flops(300) == per_key * roofline.causal_ctx_sum(300)
    # decode: step d (1..D) of a slot with `len` cached keys reads len + d
    lens, D = np.array([10.0, 95.0, 500.0]), 8
    want = sum(min(L + d, 100) for L in lens for d in range(1, D + 1))
    assert roofline.decode_keys(lens, D, 100) == want
    assert roofline.decode_keys(lens, D, 0) == \
        sum(L + d for L in lens for d in range(1, D + 1))
    assert roofline.decode_keys(lens, D, 10**6) == \
        roofline.decode_keys(lens, D, 0)
    # the two kinds side by side count each layer by its own window
    both = roofline.Stack([full, win], 50)
    assert both.attn_flops(ctx(300)) == \
        F.attn_flops(ctx(300)) + W.attn_flops(ctx(300))
    fb, bb = both.decode_attn_cost(lambda w: 1000 if w else 3000, 24)
    ff, bf = F.decode_attn_cost(lambda w: 3000, 24)
    fw, bw = W.decode_attn_cost(lambda w: 1000, 24)
    assert (fb, bb) == (ff + fw, bf + bw)


class _Trace:
    def __init__(self, secs):
        self.secs = secs

    def time_of(self, pattern):
        return self.secs


def _reader_ctx():
    """A fixed reader context at r1-distill-qwen-1.5b's widths."""
    rng = np.random.default_rng(0)
    calls = []
    for i in range(40):
        lens = rng.integers(400, 2400, 60).astype(np.float64)
        calls.append((i < 15, lens, 8))
    batches = []
    for _ in range(3):
        mask = np.zeros((32, 2496), np.float32)
        plen, rlen = rng.integers(400, 449, 32), rng.integers(1, 2049, 32)
        for n in range(32):
            mask[n, plen[n]:plen[n] + rlen[n]] = 1.0
        batches.append({"loss_mask": mask,
                        "total_lens": (plen + rlen).astype(np.int32)})
    cfg = _r1()
    w = {"engine": {"generated": 19000}, "window_s": 10.34,
         "trace_stop_s": 1.21, "prompt_tokens": 3400, "prompts": 8,
         "batches": batches, "traced_steps": 2}
    return {"cfg": cfg, "family": spec.family(cfg), "w": w,
            "trace": _Trace(0.05), "peaks": roofline.PEAKS["TPU v5 lite"],
            "chips": 1, "decode_calls": calls}


@pytest.mark.parametrize("metric,want", [
    ("step.mfu", 9.11394533618739),
    ("kernel.decode_attn.roofline", 52.00001531623931)])
def test_readers_equal_golden(metric, want):
    assert spec.load_reader(metric)(_reader_ctx()) == want


def test_peaks():
    assert roofline.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_fused_loss_counts_scored_rows_not_padding():
    """A padded batch: the fused loss's roofline counts only the positions
    the loss scores (its mask), and only the traced steps' batches."""
    import numpy as np
    cfg = {"hidden_size": 64, "vocab_size": 512}
    mask = np.zeros((4, 96), np.float32)
    mask[0, 40:50] = 1.0               # 10 scored tokens, the rest prompt
    mask[1, 40:45] = 1.0               # or padding
    traced = {"tokens": np.zeros((4, 96), np.int32), "loss_mask": mask}
    later = {"tokens": np.zeros((4, 96), np.int32),
             "loss_mask": np.ones((4, 96), np.float32)}
    p = roofline.PEAKS["TPU v5 lite"]
    secs = 1e-6
    ctx = {"cfg": cfg, "trace": _Trace(secs), "peaks": p,
           "w": {"batches": [traced, later], "traced_steps": 1}}
    got = spec.load_reader("kernel.fused_is_grpo.roofline")(ctx)
    flops, byts = roofline.fused_is_grpo_cost(cfg, 15)
    want = 100.0 * max(flops / p["flops_bf16"], byts / p["hbm_bytes_s"]) / secs
    assert got == pytest.approx(want, rel=1e-12)
    padded = roofline.fused_is_grpo_cost(cfg, 4 * 95)
    assert got < 100.0 * max(padded[0] / p["flops_bf16"],
                             padded[1] / p["hbm_bytes_s"]) / secs
    ctx["w"]["traced_steps"] = 0
    assert spec.load_reader("kernel.fused_is_grpo.roofline")(ctx) is None
