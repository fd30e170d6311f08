"""The ``qwen2`` family: a dense decoder of like full-attention blocks
(RMSNorm, grouped-query attention with rotary positions, optional per-head
q/k norm, SwiGLU MLP), final RMSNorm, tied or untied head.

A configuration file names its family (``"family"``); everything in the
harness that depends on the model goes through that family's module,
``bench/families/<family>.py``, found by name:

- ``model_config(cfg, name)``: the system's ``ModelConfig``;
- ``design(cfg, traffic)``, ``make_params(cfg, traffic, key)``: seeded
  weights with the shaped stop token, in the trainer's layout;
- ``reference_run(cfg, hyper, params, batches, reward_of, precision,
  make_params)``: the plain float32 reference following the trainer;
- ``counts(c)``: the ``roofline.Stack`` the metric readers count with,
  from the reader's context ``c`` (a family whose work depends on routing
  can read the program's counters there).

A new architecture is a configuration file and a family module. This one
is ``bench/weights.py`` and ``bench/reference.py``.
"""
from __future__ import annotations

from bench import reference, roofline, weights

design = weights.design
make_params = weights.make_params


def model_config(cfg: dict, name: str):
    """The system's ``ModelConfig`` for a configuration file (HF keys)."""
    from repro.common.config import ModelConfig
    return ModelConfig(
        name=name, family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=("attn",), qk_norm=cfg["qk_norm"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], param_dtype=cfg["param_dtype"],
        source=cfg["source"])


def reference_run(cfg, hyper, params, batches, reward_of, precision,
                  make_params):
    return reference.run(cfg, hyper, params, batches, reward_of,
                         precision=precision, make_params=make_params)


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one decoder layer (norm scales left out)."""
    d, H, KV, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    return d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff


def counts(c: dict) -> roofline.Stack:
    cfg = c["cfg"]
    layers = roofline.Layers(
        n=cfg["num_hidden_layers"], window=0,
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        params=layer_params(cfg))
    return roofline.Stack([layers], cfg["hidden_size"] * cfg["vocab_size"])
