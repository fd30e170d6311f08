"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
is one JSON file found by name (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), its correctness limits are
``bench/limits/<cell>.json``, and each per-layer metric is a reader
``bench/metrics/<metric>.py``. A configuration file names its family
(``"family"``), a module ``bench/families/<family>.py`` through which
everything that depends on the model goes. Adding any of them is adding
files.
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
    """Each compared number's limit (None: not compared); none (so nothing
    passes) for a cell whose limits have not been set."""
    path = bench_dir / "limits" / f"{cell_name}.json"
    if not path.exists():
        return {}
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<metric_name>.py``."""
    path = bench_dir / "metrics" / f"{metric_name}.py"
    return _load_module(
        path, "bench_metric_" + metric_name.replace(".", "_")).read


_FAMILIES: dict = {}


def load_family(family_name: str, bench_dir: Path = BENCH):
    """The module ``bench/families/<family_name>.py``, loaded once per
    file, so its jitted functions keep their caches across calls."""
    path = (bench_dir / "families" / f"{family_name}.py").resolve()
    if path not in _FAMILIES:
        _FAMILIES[path] = _load_module(path, "bench_family_" + family_name)
    return _FAMILIES[path]


def family(cfg: dict, bench_dir: Path = BENCH):
    """The family module a configuration file names."""
    return load_family(cfg["family"], bench_dir)


def model_config(cfg: dict, name: str, bench_dir: Path = BENCH):
    """The system's ``ModelConfig`` for a configuration file, from its
    family."""
    return family(cfg, bench_dir).model_config(cfg, name)
