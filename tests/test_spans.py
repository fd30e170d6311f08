"""Phase spans and the re-prefill counter, on the tiny model.

* every step record carries the engine's and the update's phase seconds,
  and each phase lies inside the interval that holds it;
* ``reprefill_tokens`` counts exactly the prompt and response so far of
  every partial resumed by re-prefill, and nothing for fresh prompts or a
  ``kv_snapshot`` restore;
* under the profiler, the program's spans sit inside the benchmark's
  ``bench.step`` and name the device's idle gaps.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.common.config import RolloutConfig, TrainConfig
from repro.common.spans import Span
from repro.configs import get_config
from repro.core.copris import CoPRISTrainer
from repro.core.rollout import RolloutEngine
from repro.data.tasks import AdditionTask, EOS
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:           # the benchmark's trace reduction
    sys.path.insert(0, str(ROOT))

CFG = get_config("tiny")
RO = dict(batch_size=4, group_size=2, max_prompt_len=16, max_response_len=12,
          concurrency=8, mode="copris")
ENGINE_PHASES = ("prefill_time", "decode_time", "replay_time")
UPDATE_PHASES = ("pack_time", "train_time")


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def trainer(params):
    tr = CoPRISTrainer(CFG, RolloutConfig(**RO),
                       TrainConfig(lr=2e-4, warmup_steps=2, microbatches=1),
                       AdditionTask(max_value=9, seed=0), eos_id=EOS,
                       params=jax.tree.map(jnp.copy, params))
    yield tr
    tr.close()


def test_span_accumulates_and_labels_only():
    stats = {}
    for _ in range(3):
        with Span("engine.decode", stats, "decode_time"):
            pass
    assert stats["decode_time"] >= 0.0 and list(stats) == ["decode_time"]
    with Span("engine.end_stage"):       # a trace label, nothing to add
        pass
    assert list(stats) == ["decode_time"]
    with pytest.raises(ValueError):      # an exception leaves the span
        with Span("trainer.pack", stats, "pack_time"):
            raise ValueError
    assert stats["pack_time"] >= 0.0


def test_step_record_carries_phase_seconds(trainer):
    for _ in range(2):
        out = trainer.step()
        for k in ENGINE_PHASES + UPDATE_PHASES:
            assert out[k] > 0.0, k
        assert sum(out[k] for k in ENGINE_PHASES) <= out["rollout_time"]
        assert sum(out[k] for k in UPDATE_PHASES) <= out["update_time"]
    # the engine's lifetime totals sum the per-stage phase seconds too
    totals = trainer.engine.stats_snapshot()
    for k in ENGINE_PHASES:
        assert totals[k] >= sum(h[k] for h in trainer.history)
    for gone in ("prefill_calls", "batch_trajs", "buffer_waiting"):
        assert gone not in totals


def _partials(engine):
    """Buffered partials (response tokens, not done) with the length a
    re-prefill would run over: prompt plus response so far."""
    return [(t, len(t.prompt_tokens) + len(t.response_tokens))
            for g in engine.buffer.groups() for t in g.trajectories
            if not t.done and t.response_tokens]


@pytest.mark.parametrize("strategy", ["reprefill", "kv_snapshot"])
def test_reprefill_tokens_count_resumed_partials(params, strategy):
    task = AdditionTask(max_value=20, seed=7)
    ro = RolloutConfig(batch_size=2, group_size=2, max_prompt_len=16,
                       max_response_len=48, concurrency=3, mode="copris",
                       resume_strategy=strategy)
    engine = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
    _, st0 = engine.collect(params, 0, jax.random.PRNGKey(1))
    # the first stage has nothing to resume: every row is a fresh prompt
    assert st0["reprefill_tokens"] == 0 and st0["prefill_tokens"] > 0
    from bench import spec
    share = spec.load_reader("engine.reprefill_share")
    assert share({"w": {"engine": st0}}) == 0.0

    before = _partials(engine)
    assert before, "the first stage left no partial to resume"
    _, st1 = engine.collect(params, 1, jax.random.PRNGKey(2))
    resumed = [(t, L) for t, L in before if 1 in t.stage_ids]
    assert resumed and st1["resumed"] > 0
    want = sum(L for _, L in resumed) if strategy == "reprefill" else 0
    assert st1["reprefill_tokens"] == want
    assert st1["prefill_tokens"] >= want


def test_spans_name_idle_gaps_under_the_profiler(trainer, tmp_path):
    from bench import trace

    trainer.step()                       # every program compiled
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            trainer.step()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))

    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                spans.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    (s0, s1), = spans["bench.step"]
    for name in ("engine.decode", "engine.replay", "engine.prefill",
                 "trainer.collect", "trainer.pack", "trainer.train"):
        assert spans.get(name), name
        assert all(s0 <= s and e <= s1 for s, e in spans[name]), name

    labels = {label for _, label in trace.reduce(path).gaps}
    assert any(lb.startswith(("engine.", "trainer.")) for lb in labels), labels
