"""FLOP and byte counts on known shapes, and the peaks table."""
import dataclasses

import pytest

from bench import roofline, spec


def _cfgs():
    bench = spec.load_benchmark()
    return [(c["name"], spec.load_config(bench, c["name"]))
            for c in bench["configs"]]


@pytest.mark.parametrize("name,cfg", _cfgs())
def test_forward_flops_match_param_count(name, cfg):
    m = spec.model_config(cfg, name)
    # 2 FLOPs per matrix parameter a token passes through: every layer
    # matrix and the head; norm scales and the embedding lookup do none
    n = m.param_count() - 2 * m.d_model * m.num_layers
    if not m.tie_embeddings:
        n -= m.vocab_size * m.d_model        # the lookup table
    assert roofline.forward_token_flops(cfg, 0.0, logits=True) == 2 * n
    for L in (2, 5):
        c2 = dict(cfg, num_hidden_layers=L)
        m2 = dataclasses.replace(m, num_layers=L)
        grown = (m2.param_count() - m.param_count()
                 - 2 * m.d_model * (L - m.num_layers))
        assert (roofline.forward_token_flops(c2, 0.0, logits=False)
                - roofline.forward_token_flops(cfg, 0.0, logits=False)
                == 2 * grown)


def test_known_shapes():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 2, "num_hidden_layers": 3, "vocab_size": 10}
    # QK^T and PV: 2 * 2 FLOPs per head dim per key, 4 heads, 3 layers
    assert roofline.attn_flops(cfg, 5) == 4 * 3 * 4 * 2 * 5
    f, b = roofline.decode_attn_cost(cfg, ctx_sum=100, tokens=10)
    assert f == 4 * 3 * 4 * 2 * 100
    # K and V of 2 heads x 2 dims in bf16 per key, q and out per token
    assert b == 3 * (2 * 2 * 2 * 2 * 100 + 2 * 10 * 4 * 2 * 2)
    f, b = roofline.fused_is_grpo_cost(cfg, rows=7)
    assert f == 6 * 7 * 8 * 10
    assert roofline.causal_ctx_sum(4) == 10
    one = roofline.train_flops(cfg, [4], [2])
    assert one == 3 * (2 * 3 * roofline.layer_params(cfg) * 4
                       + roofline.attn_flops(cfg, 1) * 10
                       + 2 * 8 * 10 * 2)


def test_peaks():
    assert roofline.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


class _Trace:
    def __init__(self, secs):
        self.secs = secs

    def time_of(self, pattern):
        return self.secs


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
