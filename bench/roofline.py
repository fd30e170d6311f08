"""Peaks of the chips the benchmark runs on, and the operations and bytes
that the measured work requires, computed from shapes.

Only what the algorithm needs is counted: no padding, no recomputation
(remat, re-prefill of resumed partials), no over-generation.

A model is counted as a ``Stack``: groups of like layers, each with the
keys its attention reads at most (its window, 0 for all) and the matrix
parameters a token passes through in it (attention projections and its
FFN work per token), and the head. A configuration's family module
(``bench/families/<family>.py``) builds the stack from the configuration
file; what is here holds for every family.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def causal_ctx_sum(n: int) -> float:
    """Σ keys attended over a causal sequence of ``n`` tokens."""
    return n * (n + 1) / 2.0


def causal_keys(n, window: int) -> float:
    """Σ keys attended over a causal sequence of ``n`` tokens in a layer
    that reads at most ``window`` keys (0: every key)."""
    if not window or n <= window:
        return causal_ctx_sum(n)
    return causal_ctx_sum(window) + (n - window) * window


def decode_keys(lens, D: int, window: int = 0) -> float:
    """Σ keys attended by one decode chunk of ``D`` steps over slots whose
    caches hold ``lens`` keys: step d (1..D) of a slot reads ``len + d``
    keys, at most ``window`` of them (0: every key)."""
    lens = np.asarray(lens, np.float64)
    if not window:
        return D * lens.sum() + lens.size * D * (D + 1) / 2.0
    return float(np.minimum(lens[:, None] + np.arange(1, D + 1),
                            window).sum())


def decode_key_sum(c: dict, window: int, *, traced: bool) -> float:
    """Σ keys the decoded tokens of a reader's context attend in a layer
    with ``window`` (0: every key), over the traced decode calls or all of
    them. ``c["decode_calls"]`` holds each call's ``(traced, lens, D)``."""
    return sum(decode_keys(lens, D, window)
               for t, lens, D in c["decode_calls"] if t or not traced)


def decode_tokens(c: dict, *, traced: bool) -> int:
    """Tokens decoded (live slot-steps) over the traced decode calls or all
    of them."""
    return sum(lens.size * D
               for t, lens, D in c["decode_calls"] if t or not traced)


@dataclasses.dataclass(frozen=True)
class Layers:
    """``n`` like decoder layers. ``window``: the keys a token's attention
    reads at most (0: all). ``params``: matrix parameters a token passes
    through in one layer (2 FLOPs each), attention projections and the
    FFN work of one token."""
    n: int
    window: int
    heads: int
    kv_heads: int
    head_dim: int
    params: float


Keys = Callable[[int], float]     # a layer's window (0: all) -> keys read


class Stack:
    """The counts of one model: ``layers`` and a head of ``head_params``."""

    def __init__(self, layers: Sequence[Layers], head_params: int):
        self.layers = tuple(layers)
        self.head_params = head_params

    def head_flops(self) -> float:
        return 2.0 * self.head_params

    def attn_flops(self, keys: Keys) -> float:
        """Forward attention FLOPs of one token (QK^T and PV), every layer;
        ``keys(window)``: the keys a layer with that window reads."""
        return sum(4.0 * g.n * g.heads * g.head_dim * keys(g.window)
                   for g in self.layers)

    def forward_token_flops(self, keys: Keys, *, logits: bool) -> float:
        """One token through the stack, with or without the head."""
        f = (sum(2.0 * g.n * g.params for g in self.layers)
             + self.attn_flops(keys))
        return f + (self.head_flops() if logits else 0.0)

    def causal_attn_flops(self, n) -> float:
        """Forward attention FLOPs over a causal sequence of ``n`` tokens."""
        return sum(4.0 * g.n * g.heads * g.head_dim
                   * causal_keys(n, g.window) for g in self.layers)

    def train_flops(self, total_lens, response_lens) -> float:
        """Forward and backward (3x forward) over the real tokens of a
        batch: the stack over every prompt and response token, the head
        only where a response token is predicted."""
        f = 0.0
        for n, r in zip(total_lens, response_lens):
            f += 3.0 * (sum(2.0 * g.n * g.params * n for g in self.layers)
                        + self.causal_attn_flops(n)
                        + 2.0 * self.head_params * r)
        return f

    def decode_attn_cost(self, key_sum: Keys, tokens: int,
                         kv_bytes: int = 2) -> tuple:
        """(FLOPs, bytes) of the decode-attention kernel over ``tokens``
        decoded tokens that attend ``key_sum(window)`` keys in all in a
        layer with that window: each reads its slot's live K and V once,
        its query, and writes its output."""
        flops = self.attn_flops(key_sum)
        byts = sum(g.n * (2.0 * g.kv_heads * g.head_dim * kv_bytes
                          * key_sum(g.window)
                          + 2.0 * tokens * g.heads * g.head_dim * kv_bytes)
                   for g in self.layers)
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
