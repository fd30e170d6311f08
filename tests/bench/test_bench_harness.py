"""BENCHMARK.json and the files it names: every cell finds its files, every
metric its reader, and adding a cell, a configuration (with a family of
its own) or a metric is adding files."""
import hashlib
import json
import re
import shutil

import pytest

from bench import spec
from bench_tiny import BENCH, make_root

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


def test_new_traffic_file_is_a_new_cell(tmp_path, capsys):
    """A cell added by files alone runs end to end (CPU, tiny widths) and
    reports every end-to-end metric; one named ``<name>.<suffix>`` (a
    bound of its own for some cells) reports ``<name>``."""
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
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    want = [m["name"] for m in spec.load_benchmark(root)["end_to_end"]]
    assert sorted(got["metrics"]) == sorted(want)
    suffixed = [n for n in want if "." in n]
    assert suffixed
    for n in suffixed:
        assert got["metrics"][n]["value"] == \
            got["metrics"][n.split(".")[0]]["value"]


def _tree_digest(path):
    h = hashlib.sha256()
    for f in sorted(path.rglob("*")):
        if f.is_file() and "__pycache__" not in f.parts:
            h.update(str(f.relative_to(path)).encode() + f.read_bytes())
    return h.hexdigest()


def test_new_family_is_new_files(tmp_path, capsys):
    """A configuration whose family module is its own file (here a renamed
    copy of ``qwen2``) runs end to end with no file of ``bench/`` changed,
    and checks exactly what the ``qwen2`` cell checks."""
    from bench import run
    before = _tree_digest(BENCH)
    root = make_root(tmp_path, cells=(("tiny.mix", "tiny", "mix"),
                                      ("copy.mix", "copy", "mix")),
                     limits={"loss_gap": 1, "grad_gap": 1, "update_gap": 1,
                             "behaviour_gap": 1})
    fams = root / "bench" / "families"
    shutil.copy(BENCH / "families" / "qwen2.py", fams / "qwen2copy.py")
    cfg_path = root / "bench" / "configs" / "copy.json"
    cfg = dict(json.loads(cfg_path.read_text()), family="qwen2copy")
    cfg_path.write_text(json.dumps(cfg))
    fam = spec.family(cfg, root / "bench")
    assert fam.__file__ == str(fams / "qwen2copy.py")
    assert fam is not spec.load_family("qwen2", root / "bench")
    checks = {}
    for cell in ("tiny.mix", "copy.mix"):
        assert run.main(["--workload", cell, "--seed", "7", "--seconds",
                         "1"], require_tpu=False, root=root) == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        checks[cell] = json.loads(out)["checks"]
    assert checks["copy.mix"] == checks["tiny.mix"]
    assert _tree_digest(BENCH) == before


@pytest.mark.parametrize("traffic,want", [("longtail", [2240, 2304, 2368,
                                                       2432, 2496]),
                                          ("short", [704])])
def test_warmed_train_lengths(traffic, want):
    """The warm-up runs the train lengths a batch of the mix is padded to:
    the longtail's five (a batch under 2240 is rarer than 1 in 400), and
    the short mix's one (every response runs to the cap)."""
    from bench import run
    from repro.core.rollout import PREFILL_BUCKET
    b = spec.load_benchmark()
    cfg = spec.load_config(b, "r1-distill-qwen-1.5b")
    tf = spec.load_traffic(traffic)
    p = spec.family(cfg).design(cfg, tf)["stop_prob_effective"]
    max_len = -(-(tf["prompt_len_max"] + tf["max_response_len"])
                // PREFILL_BUCKET) * PREFILL_BUCKET
    assert list(run.train_lens(tf, p, max_len, PREFILL_BUCKET)) == want


def test_refuses_without_a_tpu(tmp_path, capsys):
    from bench import run
    root = make_root(tmp_path)
    assert run.main(["--workload", "tiny.mix", "--seed", "1", "--seconds",
                     "1"], root=root) != 0
    assert capsys.readouterr().out == ""


def test_behaviour_worst_locates_the_widest_gaps():
    """The log's look at the behaviour gaps: each widest token's place,
    whether a prefill produced it (a response's first token, or a stage's
    first after a re-prefill), whether it is the stop token."""
    import numpy as np
    from bench import run
    P, T = 3, 10
    tokens = np.zeros((2, T), np.int32)
    tokens[0, 5] = 1                                  # the stop token
    mask = np.zeros((2, T), np.float32)
    mask[:, P:8] = 1.0
    stages = np.full((2, T), -1, np.int32)
    stages[:, P:8] = 0
    stages[1, 6:8] = 1                                # resumed at 6
    ref = np.full((2, T - 1), np.nan, np.float32)
    ref[:, P - 1:7] = 0.001
    ref[0, 4], ref[1, 5], ref[1, 2] = 0.05, 0.03, 0.02
    bt = dict(tokens=tokens, loss_mask=mask, stage_ids=stages,
              behaviour_logp=np.zeros((2, T), np.float32),
              prompt_lens=np.array([P, P]), total_lens=np.array([8, 8]))
    got = run.behaviour_worst({"batches": [bt]}, {"ref_logp": [ref]}, 1)
    where = [(w["row"], w["pos"], w["resp"], w["prefill"], w["stop"],
              w["since_prefill"]) for w in got["worst"]]
    assert where == [(0, 5, 2, False, True, 2), (1, 6, 3, True, False, 0),
                     (1, 3, 0, True, False, 0)]
    assert got["worst"][0]["gap"] == pytest.approx(0.05)
    assert got["n_over"] == {"prefill": 2, "decode": 1}
