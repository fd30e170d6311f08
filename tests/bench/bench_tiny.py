"""A tiny cell (same architecture, small widths) in a scratch root, for
running the harness end to end on the CPU."""
from __future__ import annotations

import json
import os
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"

TINY_CFG = {
    "source": "tiny CPU stand-in", "model_type": "qwen3", "family": "qwen2",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "qk_norm": True,
    "torch_dtype": "bfloat16", "param_dtype": "float32", "reduced": [],
}

TINY_TRAFFIC = {
    "batch_size": 2, "group_size": 4, "concurrency": 8,
    "prompt_len_min": 8, "prompt_len_max": 16, "max_response_len": 32,
    "stop_prob": 0.0625, "temperature": 1.0, "decode_chunk": 4,
    "mode": "copris", "kv_backend": "dense", "lr": 1e-6,
    "warmup_steps": 10, "reward": "parity", "eos_id": 1, "logit_std": 0.1,
    "clip_low": 0.2, "clip_high": 0.28, "is_ratio_cap": 10.0,
    "weight_decay": 0.01, "betas": [0.9, 0.999], "adam_eps": 1e-8,
    "grad_clip": 1.0, "sample_seed": 0,
}


def make_root(tmp: Path, *, cfg=None, traffic=None, limits=None,
              cells=(("tiny.mix", "tiny", "mix"),)) -> Path:
    """A root holding BENCHMARK.json (the real one's metrics, with the
    given cells) and a ``bench`` directory whose configs, traffic and
    limits are the tiny ones; metric readers are the real files, and so
    are the family modules (a test may add its own beside them)."""
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp / "bench"
    for d in ("configs", "traffic", "limits", "families"):
        (b / d).mkdir(parents=True, exist_ok=True)
    os.symlink(BENCH / "metrics", b / "metrics")
    for f in (BENCH / "families").glob("*.py"):
        os.symlink(f, b / "families" / f.name)
    cfgs = {}
    for name, c, t in cells:
        cfgs[c] = {"name": c, "source": "tiny", "file": f"bench/configs/{c}.json",
                   "reduced": [], "why": "CPU test"}
        (b / "configs" / f"{c}.json").write_text(json.dumps(cfg or TINY_CFG))
        (b / "traffic" / f"{t}.json").write_text(
            json.dumps(traffic or TINY_TRAFFIC))
        if limits is not None:
            (b / "limits" / f"{name}.json").write_text(json.dumps(
                {k: {"limit": v} for k, v in limits.items()}))
    real["configs"] = list(cfgs.values())
    real["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "CPU test"} for n, c, t in cells]
    for m in real["per_layer"] + real["end_to_end"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(real))
    return tmp
