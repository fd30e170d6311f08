"""BENCHMARK.json and the files it names: every cell finds its files, every
metric its reader, and adding a cell, a configuration or a metric is adding
files."""
import json
import re

import pytest

from bench import spec
from bench_tiny import make_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_contract_shape():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_every_file_is_there_and_loads():
    b = spec.load_benchmark()
    for c in b["configs"]:
        cfg = spec.load_config(b, c["name"])
        assert set(c["reduced"]) <= set(cfg["published"])
        assert set(cfg["reduced"]) == set(c["reduced"])
        spec.model_config(cfg, c["name"])
    for w in b["workloads"]:
        spec.config_entry(b, w["config"])
        spec.load_traffic(w["traffic"])
        lim = spec.load_limits(w["name"])
        assert set(lim) == {"loss_gap", "grad_gap", "update_gap",
                            "behaviour_gap"}, w["name"]
    for m in b["per_layer"]:
        assert callable(spec.load_reader(m["name"]))


def test_moves_reported_by_every_listed_cell():
    b = spec.load_benchmark()
    for m in b["per_layer"]:
        cells = m.get("workloads", [w["name"] for w in b["workloads"]])
        for c in cells:
            got = {x["name"] for x in spec.metrics_for(b, c, "end_to_end")}
            assert m["moves"] in got, (m["name"], c)
    for w in b["workloads"]:
        kinds = {x["name"] for x in spec.metrics_for(b, w["name"],
                                                     "end_to_end")}
        assert "setup_s" in kinds and len(kinds) >= 2
        assert spec.metrics_for(b, w["name"], "per_layer")


def test_new_traffic_file_is_a_new_cell(tmp_path):
    """A cell added by files alone runs end to end (CPU, tiny widths)."""
    from bench import run
    root = make_root(tmp_path, cells=(("tiny.mix", "tiny", "mix"),
                                      ("tiny.other", "tiny", "other")),
                     limits={"loss_gap": 1, "grad_gap": 1, "update_gap": 1,
                             "behaviour_gap": 1})
    assert [w["name"] for w in spec.load_benchmark(root)["workloads"]] == \
        ["tiny.mix", "tiny.other"]
    assert run.main(["--workload", "tiny.other", "--seed", "5",
                     "--seconds", "1", "--trace", "0"],
                    require_tpu=False, root=root) == 0


def test_refuses_without_a_tpu(tmp_path, capsys):
    from bench import run
    root = make_root(tmp_path)
    assert run.main(["--workload", "tiny.mix", "--seed", "1", "--seconds",
                     "1"], root=root) != 0
    assert capsys.readouterr().out == ""
