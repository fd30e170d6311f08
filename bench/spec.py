"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
is one JSON file found by name (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), its correctness limits are
``bench/limits/<cell>.json``, and each per-layer metric is a reader
``bench/metrics/<metric>.py``. Adding any of them is adding files.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    return json.loads((root / config_entry(bench, name)["file"]).read_text())


def load_traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def load_limits(cell_name: str, bench_dir: Path = BENCH) -> dict:
    """Each compared number's limit; none (so nothing passes) for a cell
    whose limits have not been set."""
    path = bench_dir / "limits" / f"{cell_name}.json"
    if not path.exists():
        return {}
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(metric_name: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<metric_name>.py``."""
    path = bench_dir / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
