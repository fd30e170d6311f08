"""Peaks of the chips the benchmark runs on, and the operations and bytes
that the measured work requires, computed from shapes.

Only what the algorithm needs is counted: no padding, no recomputation
(remat, re-prefill of resumed partials), no over-generation.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one decoder layer (norm scales left out)."""
    d, H, KV, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    return d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops(cfg: dict, ctx: float) -> float:
    """Forward attention FLOPs of one token over ``ctx`` keys, all layers
    (QK^T and PV)."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * ctx)


def forward_token_flops(cfg: dict, ctx: float, *, logits: bool) -> float:
    """One token through the stack, attending ``ctx`` keys, with or without
    the head."""
    f = 2.0 * cfg["num_hidden_layers"] * layer_params(cfg) + attn_flops(cfg, ctx)
    return f + (2.0 * head_params(cfg) if logits else 0.0)


def causal_ctx_sum(n: int) -> float:
    """Σ keys attended over a causal sequence of ``n`` tokens."""
    return n * (n + 1) / 2.0


def train_flops(cfg: dict, total_lens, response_lens) -> float:
    """Forward and backward (3x forward) over the real tokens of a batch:
    the stack over every prompt and response token, the head only where a
    response token is predicted."""
    f = 0.0
    for n, r in zip(total_lens, response_lens):
        f += 3.0 * (2.0 * cfg["num_hidden_layers"] * layer_params(cfg) * n
                    + attn_flops(cfg, 1.0) * causal_ctx_sum(n)
                    + 2.0 * head_params(cfg) * r)
    return f


def decode_attn_cost(cfg: dict, ctx_sum: float, tokens: int,
                     kv_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of the decode-attention kernel over ``tokens`` decoded
    tokens whose keys number ``ctx_sum`` in all, every layer: each reads
    its slot's live K and V once, its query, and writes its output."""
    L, H, KV, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    flops = 4.0 * L * H * hd * ctx_sum
    byts = L * (2.0 * KV * hd * kv_bytes * ctx_sum
                + 2.0 * tokens * H * hd * kv_bytes)
    return flops, byts


def fused_is_grpo_cost(cfg: dict, rows: float, w_bytes: int = 4,
                       h_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of the fused IS+GRPO loss, forward and backward,
    over ``rows`` positions of width d against the (d, V) head: the logits
    once forward, and dh and dW backward (6·rows·d·V); the head read once
    and dW written once, hidden states read and dh written once."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    flops = 6.0 * rows * d * V
    byts = (2.0 * d * V * w_bytes + 2.0 * rows * d * h_bytes
            + rows * 4 * 8)
    return flops, byts
