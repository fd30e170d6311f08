"""The phase metrics' readers on hand-built window records: each step's
phase seconds averaged over the window's steps, the re-prefill share from
the engine's counters, and nothing where a program lacks the span or the
counter."""
import pytest

from bench import spec

STEPS = [
    {"prefill_time": 0.30, "decode_time": 2.50, "replay_time": 0.40,
     "pack_time": 0.05, "rollout_time": 3.4},
    {"prefill_time": 0.10, "decode_time": 2.10, "replay_time": 0.20,
     "pack_time": 0.07, "rollout_time": 2.5},
]

PER_STEP = [("engine.prefill_s", "prefill_time", 0.20),
            ("engine.decode_s", "decode_time", 2.30),
            ("engine.replay_s", "replay_time", 0.30),
            ("trainer.pack_s", "pack_time", 0.06)]


@pytest.mark.parametrize("metric,key,want", PER_STEP)
def test_phase_seconds_per_step(metric, key, want):
    read = spec.load_reader(metric)
    assert read({"w": {"steps": STEPS}}) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric,key,want", PER_STEP)
def test_phase_seconds_absent(metric, key, want):
    """A program without the span (an older parent) gives nothing."""
    read = spec.load_reader(metric)
    older = [{k: v for k, v in s.items() if k != key} for s in STEPS]
    assert read({"w": {"steps": older}}) is None
    assert read({"w": {"steps": STEPS[:1] + older[1:]}}) is None
    assert read({"w": {"steps": []}}) is None


def test_reprefill_share():
    read = spec.load_reader("engine.reprefill_share")
    eng = {"prefill_tokens": 200_000, "reprefill_tokens": 150_000}
    assert read({"w": {"engine": eng}}) == pytest.approx(75.0, rel=1e-12)
    eng = {"prefill_tokens": 4_000, "reprefill_tokens": 0}
    assert read({"w": {"engine": eng}}) == 0.0


def test_reprefill_share_absent():
    read = spec.load_reader("engine.reprefill_share")
    assert read({"w": {"engine": {"prefill_tokens": 4_000}}}) is None
    eng = {"prefill_tokens": 0, "reprefill_tokens": 0}
    assert read({"w": {"engine": eng}}) is None


def test_phase_metrics_are_declared():
    b = spec.load_benchmark()
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for metric, _, _ in PER_STEP:
        m = per_layer[metric]
        assert (m["unit"], m["better"], m["moves"]) == ("s", "lower", "step_s")
        assert m["source"] == "program_counter" and "workloads" not in m
    m = per_layer["engine.reprefill_share"]
    assert (m["unit"], m["moves"]) == ("%", "trained_tokens_per_s")
