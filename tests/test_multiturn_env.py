"""Multi-turn environment rollouts: env protocol unit tests, the
single-turn adapter and task mixtures, turn-segmented packing (loss mask /
stage ids), engine-level slot yielding with mid-episode partial recycling
(dense and paged KV), async env/reward worker timeout + exception
isolation, and the overlapped trainer end to end.

The core guarantees under test:
* env tokens are provably excluded from the loss/IS ratio — role 0,
  behaviour logp 0, stage -1, loss_mask 0 by construction;
* a trajectory awaiting its environment owns no slot and is never
  redispatched until the observation lands;
* episodes preempted between turns resume bit-exactly across stages and
  across KV backends;
* a hung or raising env/reward fn ends the episode (or scores 0) instead
  of wedging the stage.
"""
import time

import jax
import numpy as np
import pytest

from repro.common.config import RolloutConfig
from repro.configs import get_config
from repro.core.buffer import TrajectoryBuffer
from repro.core.importance import pack_groups
from repro.core.reward_worker import AsyncEnvWorker, AsyncRewardWorker
from repro.core.rollout import RolloutEngine
from repro.core.trajectory import Group
from repro.data.tasks import (AdditionTask, CalculatorToolEnv, EOS,
                              Environment, MultiStepMathEnv,
                              MultiTurnMathTask, OBS_NO, OBS_OK, PLUS, EQ,
                              RESULT, CALL, SingleTurnEnvTask, TaskMixture)
from repro.models import model as M

CFG = get_config("tiny")
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# environment unit tests
# ---------------------------------------------------------------------------

def test_multistep_math_env():
    env = MultiStepMathEnv(start=3, deltas=(4, 5), reward_mode="exact")
    assert isinstance(env, Environment)
    prompt = env.reset()
    np.testing.assert_array_equal(prompt, [12, 3, PLUS, 4, EQ])   # BOS 3 + 4 =
    # turn 1: correct running sum 7 -> OK feedback + next delta
    obs, r, done = env.step([7, EOS])
    assert not done and r == pytest.approx(0.5)    # score 1 / num_turns 2
    np.testing.assert_array_equal(obs, [OBS_OK, PLUS, 5, EQ])
    # turn 2 (last): wrong answer -> reward 0, empty obs, done
    obs, r, done = env.step([9, EOS])
    assert done and r == 0.0 and obs.size == 0


def test_multistep_math_env_wrong_turn_recoverable():
    """The running sum advances by the TRUE delta even after a wrong
    answer, so turn 2 is still independently verifiable."""
    env = MultiStepMathEnv(start=1, deltas=(2, 3), reward_mode="exact")
    env.reset()
    obs, r, done = env.step([9, EOS])              # wrong (true sum 3)
    assert r == 0.0 and obs[0] == OBS_NO
    _, r, done = env.step([6, EOS])                # 3 + 3, still right
    assert done and r == pytest.approx(0.5)


def test_calculator_tool_env():
    env = CalculatorToolEnv(operands=(2, 3, 4), reward_mode="exact",
                            max_calls=2)
    prompt = env.reset()
    np.testing.assert_array_equal(prompt, [12, 2, PLUS, 3, PLUS, 4, EQ])
    # tool call: 2 + 3 -> RESULT 5 =
    obs, r, done = env.step([CALL, 2, PLUS, 3, EOS])
    assert not done and r == 0.0
    np.testing.assert_array_equal(obs, [RESULT, 5, EQ])
    # malformed call -> NO feedback, still no reward
    obs, r, done = env.step([CALL, PLUS, EOS])
    assert not done and r == 0.0
    np.testing.assert_array_equal(obs, [OBS_NO, EQ])
    # call budget exhausted: a CALL turn is now scored as a (wrong) answer
    obs, r, done = env.step([CALL, 2, PLUS, 4, EOS])
    assert done and r == 0.0
    # fresh episode: a non-CALL turn is the final answer
    env2 = CalculatorToolEnv(operands=(2, 3, 4), reward_mode="exact")
    env2.reset()
    _, r, done = env2.step([9, EOS])
    assert done and r == 1.0


@pytest.mark.parametrize("body,want", [
    ([2, PLUS, 3], 5),
    ([1, 2, PLUS, 3], 15),                         # multi-digit group
    ([7], 7),
    ([], None),
    ([PLUS, 3], None),                             # leading '+'
    ([2, PLUS], None),                             # trailing '+'
    ([2, EQ, 3], None),                            # non-digit token
])
def test_eval_call_edges(body, want):
    assert CalculatorToolEnv._eval_call(body) == want


def test_single_turn_adapter_equivalence():
    task = AdditionTask(max_value=20, seed=4)
    adapted = SingleTurnEnvTask(AdditionTask(max_value=20, seed=4))
    prompt, spec = adapted.sample_prompt()
    p2, answer = task.sample_prompt()
    np.testing.assert_array_equal(prompt, p2)
    env = adapted.make_env(spec)
    np.testing.assert_array_equal(env.reset(), prompt)
    resp = [1, 2, EOS]
    obs, r, done = env.step(resp)
    assert done and obs.size == 0
    assert r == pytest.approx(task.reward(resp, answer))
    assert adapted.reward(resp, spec) == pytest.approx(task.reward(resp,
                                                                   answer))


def test_task_mixture_dispatch():
    mix = TaskMixture([AdditionTask(max_value=9, seed=0),
                       MultiTurnMathTask(max_value=9, num_turns=2, seed=0)],
                      weights=[1.0, 1.0], seed=0)
    members = set()
    for _ in range(32):
        prompt, (m, inner) = mix.sample_prompt()
        members.add(m)
        env = mix.make_env((m, inner))
        assert isinstance(env, Environment)
        # member 0 rides through the adapter (one-step env), member 1 is
        # the native multi-turn env
        if m == 0:
            _, _, done = env.step([1, EOS])
            assert done
        else:
            _, _, done = env.step([1, EOS])
            assert not done
        assert 0.0 <= mix.reward([1, EOS], (m, inner)) <= 1.0
    assert members == {0, 1}, "both mixture members must be drawn"


# ---------------------------------------------------------------------------
# trajectory segmentation + packing golden tests
# ---------------------------------------------------------------------------

def _mixed_groups():
    """One group with a single-turn and a multi-turn trajectory (2 model +
    2 env + 2 model), with hand-picked logps and stages."""
    g = Group(group_id=0, prompt_tokens=np.asarray([12, 1, EQ], np.int32),
              answer=None, size=2)
    a = g.spawn()
    for tok, lp in [(5, -0.5), (6, -0.6), (EOS, -0.1)]:
        a.append(tok, lp, stage=0)
    a.done, a.finish_reason, a.reward = True, "eos", 1.0

    b = g.spawn()
    b.append(7, -0.7, stage=0)
    b.append(EOS, -0.2, stage=0)
    b.append_env([OBS_OK, EQ], stage=1)            # observation, role 0
    b.append(8, -0.8, stage=1)
    b.append(EOS, -0.3, stage=1)
    b.done, b.finish_reason, b.reward = True, "env_done", 0.5
    return [g], a, b


def test_trajectory_turn_segmentation():
    _, a, b = _mixed_groups()
    a.check_invariants()
    b.check_invariants()
    assert a.num_turns == 1 and a.model_token_count == 3
    assert b.num_turns == 2 and b.turn_starts == [0, 4]
    assert b.model_token_count == 4
    assert b.turn_tokens() == [8, EOS]
    # env tokens carry no staleness: only the 2 stage-0 MODEL tokens are
    # off-policy at stage 1
    assert b.off_policy_tokens(1) == 2
    assert b.roles == [1, 1, 0, 0, 1, 1]


def test_pack_groups_mixed_masks_golden():
    groups, a, b = _mixed_groups()
    batch = pack_groups(groups, pad_multiple=16)
    P = 3
    # row 0: single-turn — loss mask == response mask
    np.testing.assert_array_equal(batch["response_mask"][0, P:P + 3],
                                  [1, 1, 1])
    np.testing.assert_array_equal(batch["loss_mask"][0],
                                  batch["response_mask"][0])
    np.testing.assert_array_equal(batch["stage_ids"][0, P:P + 3], [0, 0, 0])
    # row 1: multi-turn — env positions are response context but NOT loss
    np.testing.assert_array_equal(batch["response_mask"][1, P:P + 6],
                                  [1, 1, 1, 1, 1, 1])
    np.testing.assert_array_equal(batch["loss_mask"][1, P:P + 6],
                                  [1, 1, 0, 0, 1, 1])
    np.testing.assert_array_equal(batch["stage_ids"][1, P:P + 6],
                                  [0, 0, -1, -1, 1, 1])
    np.testing.assert_allclose(batch["behaviour_logp"][1, P:P + 6],
                               [-0.7, -0.2, 0.0, 0.0, -0.8, -0.3])
    # padding carries nothing
    assert batch["loss_mask"][1, P + 6:].sum() == 0
    assert (batch["stage_ids"][1, P + 6:] == -1).all()
    np.testing.assert_array_equal(
        batch["tokens"][1, :P + 6],
        [12, 1, EQ, 7, EOS, OBS_OK, EQ, 8, EOS])


def test_pack_groups_sanitizes_env_positions():
    """Even if a custom trajectory recorded nonzero logps / stages on env
    tokens, the packed batch pins them to 0 / -1 — the loss's source of
    truth."""
    groups, _, b = _mixed_groups()
    b.behaviour_logps[2] = -9.9                    # corrupt an env position
    b.stage_ids[2] = 7
    batch = pack_groups(groups, pad_multiple=16)
    assert batch["behaviour_logp"][1, 3 + 2] == 0.0
    assert batch["stage_ids"][1, 3 + 2] == -1


def test_buffer_skips_awaiting_env():
    buf = TrajectoryBuffer()
    g = Group(group_id=0, prompt_tokens=np.asarray([12, EQ], np.int32),
              answer=None, size=1)
    t = g.spawn()
    t.append(5, -0.5, stage=0)
    buf.add_group(g)
    t.awaiting_env = True
    assert buf.pop_resumable(exclude=set()) is None, \
        "a parked trajectory owns no slot and must not be redispatched"
    t.awaiting_env = False
    assert buf.pop_resumable(exclude=set()) is t


# ---------------------------------------------------------------------------
# async worker: timeout + exception isolation
# ---------------------------------------------------------------------------

def test_env_worker_timeout_and_errors():
    w = AsyncEnvWorker(max_workers=2, timeout=0.15)
    w.submit("slow", time.sleep, 5.0)
    w.submit("boom", lambda: 1 / 0)
    assert not w.submit("boom", lambda: 2), "duplicate keys must be dropped"
    t0 = time.monotonic()
    results = {}
    while len(results) < 2 and time.monotonic() - t0 < 3.0:
        w.wait(0.05)
        for key, ok, val in w.poll():
            results[key] = (ok, val)
    assert time.monotonic() - t0 < 3.0, "worker deadlocked"
    ok, err = results["slow"]
    assert not ok and "exceeded" in str(err)
    ok, err = results["boom"]
    assert not ok and isinstance(err, ZeroDivisionError)
    stats = w.stats_snapshot()
    assert stats["env_timeouts"] == 1 and stats["env_errors"] == 1
    assert w.num_pending == 0
    w.shutdown()


def test_reward_worker_timeout_scores_zero():
    def hang(resp, ans):
        time.sleep(5.0)
        return 1.0

    w = AsyncRewardWorker(hang, max_workers=2, timeout=0.15)
    g = Group(group_id=0, prompt_tokens=np.asarray([12, EQ], np.int32),
              answer=3, size=1)
    t = g.spawn()
    t.append(3, -0.5, stage=0)
    t.done = True
    w.submit(t, g.answer)
    t0 = time.monotonic()
    w.gather([g])
    assert time.monotonic() - t0 < 3.0, "gather must respect the deadline"
    assert t.reward == 0.0
    assert w.stats_snapshot()["env_timeouts"] == 1
    w.shutdown()


def test_reward_worker_exception_scores_zero():
    def boom(resp, ans):
        raise RuntimeError("reward sandbox crashed")

    w = AsyncRewardWorker(boom, max_workers=2)
    g = Group(group_id=0, prompt_tokens=np.asarray([12, EQ], np.int32),
              answer=3, size=1)
    t = g.spawn()
    t.append(3, -0.5, stage=0)
    t.done = True
    w.submit(t, g.answer)
    w.gather([g])
    assert t.reward == 0.0
    assert w.stats_snapshot()["env_errors"] == 1
    w.shutdown()


# ---------------------------------------------------------------------------
# engine level: slot yielding, masked logps, partial recycling
# ---------------------------------------------------------------------------

def _mt_engine(backend="dense", *, seed=3, **kw):
    task = MultiTurnMathTask(max_value=9, num_turns=2, seed=seed)
    kw.setdefault("decode_chunk", 4)
    ro = RolloutConfig(batch_size=3, group_size=2, max_prompt_len=16,
                       max_response_len=64, concurrency=4, mode="copris",
                       kv_backend=backend, kv_page_size=16, **kw)
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS,
                        env_factory=task.make_env)
    return eng


def _tmap(groups):
    return {(g.group_id, t.sample_idx): t
            for g in groups for t in g.trajectories}


def test_engine_multiturn_collect():
    eng = _mt_engine()
    try:
        groups, stats = eng.collect(PARAMS, 0, jax.random.PRNGKey(1))
    finally:
        eng.env_worker.shutdown()
    assert len(groups) == 3
    assert stats["env_steps"] > 0
    multi = 0
    for g in groups:
        for t in g.trajectories:
            t.check_invariants()
            assert t.done and t.finish_reason in ("env_done", "length")
            # the env-accumulated return IS the reward, in [0, 1]
            assert t.reward is not None and 0.0 <= t.reward <= 1.0
            assert t.reward == pytest.approx(t.env_return)
            # env tokens: role 0, behaviour logp 0 — never sampled
            for lp, role in zip(t.behaviour_logps, t.roles):
                if role == 0:
                    assert lp == 0.0
            if t.num_turns > 1:
                multi += 1
                # a later turn exists, so an observation was integrated and
                # its turn boundary recorded
                assert t.turn_starts[1] > 0
                assert 0 in t.roles
    assert multi > 0, "expected at least one multi-turn episode"
    # env_turns counts every observation integrated this (first) stage,
    # including those of episodes evicted to the buffer at its end
    assert stats["env_turns"] == sum(
        t.num_turns - 1
        for g in list(groups) + list(eng.buffer.groups())
        for t in g.trajectories)


def test_engine_multiturn_behaviour_logps_match_policy():
    """Model tokens' buffered logps equal a recompute under the generating
    policy even ACROSS an env observation — the re-prefilled turn conditions
    on prompt + prior turns + obs exactly as the training-view forward
    does. Env tokens are skipped (never sampled)."""
    import jax.numpy as jnp

    eng = _mt_engine(seed=5)
    try:
        groups, _ = eng.collect(PARAMS, 0, jax.random.PRNGKey(2))
    finally:
        eng.env_worker.shutdown()

    def score(tokens):
        toks = jnp.asarray(tokens)[None]
        logits, _ = M.forward_train(PARAMS, CFG, toks[:, :-1], remat=False)
        lp = jax.nn.log_softmax(logits, -1)
        return np.asarray(
            jnp.take_along_axis(lp, toks[:, 1:, None], -1)[0, :, 0])

    checked_after_obs = 0
    for g in groups:
        for t in g.trajectories:
            lp = score(t.full_tokens())
            P = len(t.prompt_tokens)
            first_obs_end = (t.turn_starts[1] if t.num_turns > 1
                             else len(t.response_tokens) + 1)
            for j, (blp, role) in enumerate(zip(t.behaviour_logps, t.roles)):
                if role == 0:
                    continue
                np.testing.assert_allclose(blp, lp[P - 1 + j], atol=2e-3)
                if j >= first_obs_end:
                    checked_after_obs += 1
    assert checked_after_obs > 0, \
        "need model tokens AFTER an observation to pin the re-prefill path"


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_engine_multiturn_preempt_resume_bitexact(backend):
    """Mid-episode partial recycling: stage 0 is cut after a few chunks, so
    episodes evict between (and inside) turns; stage 1 resumes and finishes
    them. Same stage key both stages -> per-trajectory PRNG streams make
    content independent of WHERE the stage boundary fell, so dense and
    paged runs (different admission orders) must agree bit-exactly."""
    def run(be):
        eng = _mt_engine(be, seed=7)
        key = jax.random.PRNGKey(9)
        eng.begin_stage(PARAMS, 0, key)
        for _ in range(4):                   # 16 decode steps, then cut
            if not eng.step_stage(PARAMS, key):
                break
        g0, s0 = eng.end_stage()
        g1, s1 = eng.collect(PARAMS, 1, key)
        eng.env_worker.shutdown()
        return g0 + g1, s0, s1

    gd, sd0, _ = run("dense")
    gp, sp0, _ = run("paged")
    assert sd0["evicted"] > 0 and sp0["evicted"] > 0
    # mid-episode recycling really happened: a finished episode spans both
    # stages and multiple turns
    for groups in (gd, gp):
        spans = [t for g in groups for t in g.trajectories
                 if t.num_turns > 1 and len(set(t.stage_ids)) > 1]
        assert spans, "expected a multi-turn episode resumed across stages"
        for g in groups:
            for t in g.trajectories:
                t.check_invariants()
    base, got = _tmap(gd), _tmap(gp)
    common = set(base) & set(got)
    assert common
    for k in common:
        assert base[k].response_tokens == got[k].response_tokens
        assert base[k].roles == got[k].roles
        assert base[k].behaviour_logps == got[k].behaviour_logps


def test_engine_single_turn_through_env_adapter_matches_plain():
    """A single-turn task routed through the env protocol (adapter ->
    one-step episodes, slot yield + async env worker) must generate the
    SAME token content as the plain single-turn path, and its episode
    rewards must equal the task's reward fn."""
    def run(env_path):
        task = AdditionTask(max_value=20, seed=11)
        ro = RolloutConfig(batch_size=3, group_size=2, max_prompt_len=16,
                           max_response_len=20, concurrency=4, mode="copris")
        if env_path:
            adapted = SingleTurnEnvTask(AdditionTask(max_value=20, seed=11))
            eng = RolloutEngine(CFG, ro, adapted.sample_prompt, eos_id=EOS,
                                env_factory=adapted.make_env)
        else:
            eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
        groups, stats = eng.collect(PARAMS, 0, jax.random.PRNGKey(13))
        if env_path:
            eng.env_worker.shutdown()
        return groups, stats

    g_plain, _ = run(False)
    g_env, st = run(True)
    assert st["env_steps"] > 0 and st["env_turns"] == 0
    base, got = _tmap(g_plain), _tmap(g_env)
    common = set(base) & set(got)
    assert common
    task = AdditionTask(max_value=20)
    for k in common:
        assert base[k].response_tokens == got[k].response_tokens
        assert base[k].behaviour_logps == got[k].behaviour_logps
    # adapter episodes: every token is a model token, exactly one turn,
    # reward == the wrapped task's reward fn on the full response
    for g in g_env:
        for t in g.trajectories:
            assert t.num_turns == 1 and all(r == 1 for r in t.roles)
            want = task.reward(t.response_tokens, g.answer[1])
            assert t.reward == pytest.approx(want)


def test_engine_env_exception_ends_episode():
    """A raising env.step ends the episode with the reward accumulated so
    far (env_failures stat) — the stage still completes every group."""
    class BoomEnv:
        def reset(self):
            return np.asarray([12, EQ], np.int32)

        def step(self, resp):
            raise RuntimeError("sandbox crashed")

    task = AdditionTask(max_value=20, seed=2)
    ro = RolloutConfig(batch_size=2, group_size=2, max_prompt_len=16,
                       max_response_len=16, concurrency=4, mode="copris")
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS,
                        env_factory=lambda spec: BoomEnv())
    try:
        groups, stats = eng.collect(PARAMS, 0, jax.random.PRNGKey(3))
    finally:
        eng.env_worker.shutdown()
    assert len(groups) == 2
    assert stats["env_failures"] > 0
    for g in groups:
        for t in g.trajectories:
            assert t.done and t.reward == 0.0


# ---------------------------------------------------------------------------
# trainer end to end: overlapped multi-turn RL with masked loss
# ---------------------------------------------------------------------------

def test_trainer_multiturn_overlap_e2e():
    import jax.numpy as jnp

    from repro.common.config import TrainConfig
    from repro.core.copris import CoPRISTrainer

    task = MultiTurnMathTask(max_value=9, num_turns=2, seed=0)
    ro = RolloutConfig(batch_size=4, group_size=2, max_prompt_len=16,
                       max_response_len=64, concurrency=6, mode="copris",
                       env_step_timeout=10.0)
    tc = TrainConfig(lr=1e-4, warmup_steps=1, overlap=True, seed=0)
    tr = CoPRISTrainer(CFG, ro, tc, task, eos_id=EOS,
                       params=jax.tree.map(jnp.copy, PARAMS))
    try:
        hist = [tr.step() for _ in range(3)]
    finally:
        tr.close()
    assert sum(h["env_steps"] for h in hist) > 0
    assert sum(h["env_turns"] for h in hist) > 0, \
        "expected multi-turn continuations through the async env worker"
    assert all(h["env_timeouts"] == 0 for h in hist)
    # env tokens are excluded from the loss: response positions minus loss
    # positions == env-observation tokens, which carry behaviour 0 / stage -1
    b = tr.last_batch
    resp, lm = b["response_mask"], b["loss_mask"]
    env_pos = (resp > 0) & (lm == 0)
    assert env_pos.sum() > 0, "batch should contain env observations"
    assert (b["behaviour_logp"][env_pos] == 0.0).all()
    assert (b["stage_ids"][env_pos] == -1).all()
    assert (lm <= resp).all()
    # rewards are env-accumulated returns in [0, 1]
    assert (b["rewards"] >= 0.0).all() and (b["rewards"] <= 1.0).all()
