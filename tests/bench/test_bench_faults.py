"""A run with the timed path broken underneath reads ``correct`` false
(CPU, tiny widths, the first cell's limits); the sound run reads true."""
import json

import numpy as np
import pytest

from bench import run, spec
from bench_tiny import make_root


def _state_unchanged(b):
    tr = b["trainer"]
    inner = tr._train_step

    def step(params, opt_state, batch, lr):
        _, _, metrics = inner(params, opt_state, batch, lr)
        return params, opt_state, metrics

    tr._train_step = step


def _half_batch(b):
    tr = b["trainer"]
    inner = tr.train_inputs

    def inputs(batch, stage):
        jb, lr = inner(batch, stage)
        n = jb["loss_mask"].shape[0]
        jb["loss_mask"] = jb["loss_mask"].at[n // 2:].set(0.0)
        return jb, lr

    tr.train_inputs = inputs


def _token_altered(b):
    eng = b["trainer"].engine
    inner = eng._decode_chunk_fn
    eos = b["traffic"]["eos_id"]
    V = b["cfg"]["vocab_size"]

    def chunk(*a):
        cache, (tok, logp, act) = inner(*a)
        alt = 2 + (tok + 1) % (V - 2)            # never the stop token
        tok = tok.at[:, 0].set(
            np.where(tok[:, 0] == eos, tok[:, 0], alt[:, 0]))
        return cache, (tok, logp, act)

    eng._decode_chunk_fn = chunk


def _answer_altered(b):
    task = b["task"]
    inner = task.reward
    task.reward = lambda toks, ans: 1.0 - inner(toks, ans)
    b["trainer"].reward_worker.reward_fn = task.reward


FAULTS = {"sound": None, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch, "token_altered": _token_altered,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_reads_incorrect(fault, tmp_path, capsys):
    first = spec.load_benchmark()["workloads"][0]["name"]
    limits = spec.load_limits(first)
    root = make_root(tmp_path, limits=limits)
    rc = run.main(["--workload", "tiny.mix", "--seed", "2", "--seconds",
                   "1"], require_tpu=False, root=root, hooks=FAULTS[fault])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is (fault == "sound"), res["checks"]


def test_null_limit_is_not_compared():
    """A number whose limit the cell's file gives as null is reported
    apart and not judged; a number the file leaves out, or one over its
    limit, reads incorrect."""
    checks = {"loss_gap": (3e-6, None), "grad_gap": (0.01, 0.1)}
    ok, compared = run.judge(checks, {"loss_gap": None, "grad_gap": 0.1})
    assert ok and set(compared) == {"grad_gap"}
    assert not run.judge({"grad_gap": (0.01, None)}, {})[0]
    assert not run.judge({"grad_gap": (0.2, 0.1)}, {"grad_gap": 0.1})[0]
    assert not run.judge({"grad_gap": (float("nan"), 0.1)},
                         {"grad_gap": 0.1})[0]


def test_short_cell_limits():
    """The short cell compares gradient, update and behaviour, each
    limit between its system and its control or fault reading."""
    lim = json.loads((spec.BENCH / "limits" / "r1q1.5b.short.json")
                     .read_text())
    assert lim["loss_gap"]["limit"] is None
    for k in ("grad_gap", "update_gap", "behaviour_gap"):
        assert lim[k]["lower"] < lim[k]["limit"] < lim[k]["upper"], k
