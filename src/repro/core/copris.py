"""CoPRIS trainer: rollout → reward → cross-stage IS → GRPO update.

``make_train_step`` builds the *pure* training-step function (GRPO with
cross-stage IS correction, microbatched grad accumulation, AdamW). The same
function is lowered by launch/dryrun.py on the production mesh — what we
dry-run is what we train.

``CoPRISTrainer`` drives the full RL loop on a live model (the CPU-scale
end-to-end example and the integration tests). Two pipelines share one code
path:

* ``overlap=False`` — the sequential loop: collect → reward-gather → train,
  bit-identical to the historical trainer (same per-trajectory PRNG
  streams, same stage stamps).
* ``overlap=True`` — multi-step async (the Laminar / ROLL-Flash style
  overlap on top of partial rollout): a background producer thread runs
  ``RolloutEngine.collect`` against the freshest version published to the
  :class:`~repro.core.weight_sync.ParamStore` while the consumer (``step``)
  trains on a previously collected batch. Tokens carry the acquired
  version's stage id, so the existing cross-stage IS correction absorbs the
  staleness; ``max_staleness`` bounds how many optimizer updates the
  training step may be ahead of the params that generated its batch (K > 1
  lets the producer run K collects ahead). The producer owns the engine
  (and therefore the donated KV cache) exclusively.

All producer/consumer param handoff goes through the ``ParamStore``: the
consumer publishes every optimizer update as a new version, the producer
(and ``evaluate``) acquire the freshest one. With
``TrainConfig.disaggregated`` each published version is additionally
resharded from the train layout (FSDP ``data``+``model``) to the rollout
layout (``serve_tp_only``) — the versioned device-to-device weight sync a
separated rollout/train deployment needs.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig, RolloutConfig, TrainConfig
from repro.common.spans import Span
from repro.core import grpo
from repro.core.importance import pack_groups
from repro.core.rollout import RolloutEngine
from repro.core.scheduler import AdaptiveConcurrencyController
from repro.core.weight_sync import ParamStore, make_param_resharder
from repro.kernels import pallas_by_default
from repro.models import model as M
from repro.optim import adam, schedule

FUSED_VOCAB_THRESHOLD = 8192     # above this, use the vocab-blocked logp path


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig, *, use_pallas=False):
    """``use_pallas`` selects the ``fused_is_grpo`` kernels, which carry
    their own VJP. The backbone's Pallas kernels and ``fused_logprob`` are
    forward-only (no VJP), so everything else differentiated here takes
    the XLA paths: ``chunked_attention``'s custom VJP holds the blocked
    attention backward."""
    aux_coef = cfg.moe.router_aux_coef if cfg.moe is not None else 0.0
    big_vocab = cfg.vocab_size >= FUSED_VOCAB_THRESHOLD
    if big_vocab and not tcfg.fused_loss and tcfg.entropy_coef > 0.0:
        raise ValueError(
            f"entropy_coef={tcfg.entropy_coef} with fused_loss=False: the "
            f"legacy score_logprobs path cannot compute entropy above "
            f"FUSED_VOCAB_THRESHOLD={FUSED_VOCAB_THRESHOLD} (vocab_size="
            f"{cfg.vocab_size}) — the bonus would silently be dropped. "
            "Enable TrainConfig.fused_loss or set entropy_coef=0.")

    def loss_fn(params, mb):
        tokens = mb["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        # loss_mask = response positions ∧ model role: environment
        # observation tokens are masked out of the loss, the IS ratio, and
        # every mask-weighted metric (for single-turn batches it is
        # identical to the historical response mask)
        mask = mb["loss_mask"][:, 1:]
        behaviour = mb["behaviour_logp"][:, 1:]
        media = mb.get("media")
        if big_vocab and tcfg.fused_loss:
            # fused IS+GRPO loss (kernels/fused_is_grpo): ONE pass over the
            # logits computes logp, entropy and the clipped objective; the
            # custom VJP recomputes per-block stats in the backward so the
            # (B, S, V) tensor is never residualized. impl choice mirrors
            # the old score_logprobs split: Pallas on accelerators,
            # "materialize" for SPMD — under pjit the one-shot einsum lets
            # the logits shard over (data, model), while dynamic-slicing a
            # vocab-sharded weight (the blocked path) forces resharding
            # (dry-run HLO finding).
            from repro.kernels.fused_is_grpo import ops as fio_ops
            hidden, aux = M.forward_hidden(
                params, cfg, inputs, media=media, remat=tcfg.remat)
            w = M.unembed_weight(params, cfg)
            adv_tok = jnp.broadcast_to(
                mb["advantages"][:, None], targets.shape)
            loss_tok, ratio, logp_new, entropy = fio_ops.fused_is_grpo(
                hidden, w, targets, behaviour, adv_tok,
                logit_softcap=cfg.logit_softcap, clip_low=tcfg.clip_low,
                clip_high=tcfg.clip_high, use_is=tcfg.use_is_correction,
                is_ratio_cap=tcfg.is_ratio_cap,
                entropy_coef=tcfg.entropy_coef,
                impl="pallas" if use_pallas else "materialize")
            loss, metrics = grpo.aggregate_loss(
                loss_tok, ratio, logp_new, behaviour, mask,
                clip_low=tcfg.clip_low, use_is=tcfg.use_is_correction,
                loss_agg=tcfg.loss_agg)
        elif big_vocab:
            # legacy fused-logprob recompute (no entropy available —
            # entropy_coef > 0 is rejected at build time above)
            entropy = None
            logp_new, aux = M.score_logprobs(
                params, cfg, inputs, targets, media=media,
                remat=tcfg.remat, vocab_block=0)
            loss, metrics = grpo.grpo_loss(
                logp_new, behaviour, mb["advantages"], mask,
                clip_low=tcfg.clip_low, clip_high=tcfg.clip_high,
                use_is=tcfg.use_is_correction, is_ratio_cap=tcfg.is_ratio_cap,
                loss_agg=tcfg.loss_agg, entropy=entropy,
                entropy_coef=tcfg.entropy_coef)
        else:
            logits, aux = M.forward_train(params, cfg, inputs, media=media,
                                          remat=tcfg.remat)
            logp_all = jax.nn.log_softmax(logits, axis=-1)
            logp_new = jnp.take_along_axis(
                logp_all, targets[..., None], axis=-1)[..., 0]
            entropy = -(jnp.exp(logp_all) * logp_all).sum(-1)
            loss, metrics = grpo.grpo_loss(
                logp_new, behaviour, mb["advantages"], mask,
                clip_low=tcfg.clip_low, clip_high=tcfg.clip_high,
                use_is=tcfg.use_is_correction, is_ratio_cap=tcfg.is_ratio_cap,
                loss_agg=tcfg.loss_agg, entropy=entropy,
                entropy_coef=tcfg.entropy_coef)
        if entropy is not None:
            denom = jnp.maximum(mask.sum(), 1.0)
            metrics["entropy"] = (entropy * mask).sum() / denom
        total = loss + aux_coef * aux["router_aux"]
        metrics["pg_loss"] = loss
        metrics["router_aux"] = aux["router_aux"]
        return total, metrics

    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, *, use_pallas=False):
    """Returns step(params, opt_state, batch, lr) -> (params, opt_state,
    metrics). ``batch`` leaves have leading dim N = microbatches * m."""
    loss_fn = make_loss_fn(cfg, tcfg, use_pallas=use_pallas)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    k = tcfg.microbatches

    def train_step(params, opt_state, batch, lr):
        if k > 1:
            mbs = jax.tree.map(
                lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:]), batch)

            def accum(carry, mb):
                gsum, msum = carry
                (_, metrics), g = grad_fn(params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                msum = jax.tree.map(jnp.add, msum, metrics)
                return (gsum, msum), None

            mb0 = jax.tree.map(lambda a: a[0], mbs)
            (_, metrics0), g0 = grad_fn(params, mb0)
            (gsum, msum), _ = jax.lax.scan(
                accum, (g0, metrics0), jax.tree.map(lambda a: a[1:], mbs))
            grads = jax.tree.map(lambda g: g / k, gsum)
            metrics = jax.tree.map(lambda m: m / k, msum)
        else:
            (_, metrics), grads = grad_fn(params, batch)
        params, opt_state, om = adam.update(
            grads, opt_state, params, lr=lr, betas=tcfg.betas, eps=tcfg.eps,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)
        metrics.update(om)
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------


@dataclass
class _StageBatch:
    """One collected rollout stage, in flight between producer and consumer."""

    collect_idx: int        # 0-based index of this collect within the run
    params_version: int     # trainer.stage baked into the rollout params
    groups: List = field(default_factory=list)
    roll_stats: dict = field(default_factory=dict)


class ThreadSafeTask:
    """Serialises ``sample_prompt`` against the rollout producer thread.

    Tasks draw prompts from a numpy ``Generator``, which is NOT thread-safe;
    with ``overlap=True`` the producer samples prompts continuously while the
    main thread may run ``evaluate``/pass@k on the same task. Everything else
    (``reward`` etc.) passes through untouched — rewards must already be
    pure/concurrent-safe for the async reward pool.
    """

    def __init__(self, task, lock: threading.Lock):
        self._task = task
        self._lock = lock

    def sample_prompt(self):
        with self._lock:
            return self._task.sample_prompt()

    def __getattr__(self, name):
        return getattr(self._task, name)


class CoPRISTrainer:
    """Full RL loop on live hardware (CPU-scale models).

    With ``tcfg.overlap`` a background producer thread owns the rollout
    engine and feeds ``step()`` through a bounded queue; ``close()`` (or
    the context-manager exit) shuts the pipeline down. ``overlap=False``
    runs the identical logic inline and reproduces the historical
    sequential trainer bit-for-bit.
    """

    def __init__(self, model_cfg: ModelConfig, ro_cfg: RolloutConfig,
                 tcfg: TrainConfig, task, *, eos_id: int, key=None,
                 params=None, use_pallas: Optional[bool] = None,
                 train_mesh=None, rollout_mesh=None):
        self.cfg = model_cfg
        self.ro = ro_cfg
        self.tcfg = tcfg
        self.task = task
        # all trainer-originated sample_prompt calls go through this proxy
        # (producer thread during overlapped rollout, main thread during
        # evaluate) — hand it to external eval helpers too
        self.safe_task = ThreadSafeTask(task, threading.Lock())
        key = key if key is not None else jax.random.PRNGKey(tcfg.seed)
        self.key, k_init = jax.random.split(key)
        self.params = params if params is not None else M.init_params(k_init, model_cfg)
        self.opt_state = adam.init(self.params)
        # disaggregated: train and rollout each get a mesh. With several
        # devices visible a silent one-device fallback would put both sides
        # on the first device, so the caller must name the meshes.
        self.train_mesh = self.rollout_mesh = None
        if tcfg.disaggregated:
            if train_mesh is None:
                if jax.device_count() > 1:
                    raise ValueError(
                        f"disaggregated=True with {jax.device_count()} "
                        "visible devices needs train_mesh and rollout_mesh "
                        "(launch.mesh.make_disaggregated_meshes)")
                from repro.launch.mesh import make_cpu_mesh
                train_mesh = make_cpu_mesh()
            self.train_mesh = train_mesh
            self.rollout_mesh = (rollout_mesh if rollout_mesh is not None
                                 else train_mesh)
        # each side takes the Pallas kernels when it runs on one TPU device
        # (kernels.pallas_by_default), unless the caller decides
        train_pallas, rollout_pallas = (
            (pallas_by_default(self.train_mesh),
             pallas_by_default(self.rollout_mesh))
            if use_pallas is None else (use_pallas, use_pallas))
        if self.train_mesh is not None and self.train_mesh.devices.size > 1:
            # the update runs where its inputs live: place params and
            # optimizer state in the train layout (FSDP x TP)
            from repro.launch import sharding as shd
            self.params = jax.device_put(self.params, shd.params_shardings(
                self.params, self.train_mesh, cfg=model_cfg))
            self.opt_state = jax.device_put(
                self.opt_state, shd.opt_state_shardings(
                    self.params, self.train_mesh, cfg=model_cfg))
        from repro.core.reward_worker import AsyncEnvWorker, AsyncRewardWorker
        timeout = ro_cfg.env_step_timeout or None
        self.reward_worker = AsyncRewardWorker(task.reward, timeout=timeout)
        # multi-turn: a task exposing make_env(spec) routes every turn
        # through the async env pool — the engine yields decode slots while
        # episodes wait on their environments. make_env must be a pure
        # function of the spec (no task RNG), so no ThreadSafeTask guard.
        self.env_worker = None
        env_factory = None
        if hasattr(task, "make_env"):
            self.env_worker = AsyncEnvWorker(timeout=timeout)
            env_factory = task.make_env
        self.engine = RolloutEngine(model_cfg, ro_cfg,
                                    self.safe_task.sample_prompt,
                                    eos_id=eos_id, use_pallas=rollout_pallas,
                                    on_finish=self.reward_worker.submit,
                                    env_factory=env_factory,
                                    env_worker=self.env_worker)
        self._train_step = jax.jit(make_train_step(model_cfg, tcfg,
                                                   use_pallas=train_pallas))
        self.stage = 0
        self.history = []
        self.last_groups: List = []
        self.last_batch: Optional[dict] = None

        # ---- overlapped-pipeline state -------------------------------
        self.overlap = tcfg.overlap
        self.max_staleness = tcfg.max_staleness
        # how long step() may wait on the producer before declaring the
        # pipeline wedged (None = wait forever; tests set a finite value)
        self.batch_timeout: Optional[float] = None

        # ---- versioned weight sync (ParamStore) ----------------------
        # ALL producer/consumer param handoff goes through the store: the
        # consumer publishes version = stage after every update, the
        # producer / evaluate acquire the freshest. max_staleness bounds
        # the pipeline depth, so K+1 versions cover every batch still in
        # flight — older ones are dropped at publish (Laminar drop-stale).
        reshard = None
        if tcfg.disaggregated:
            reshard, _ = make_param_resharder(
                model_cfg, self.params, self.train_mesh, self.rollout_mesh)
        self.param_store = ParamStore(max_versions=self.max_staleness + 1,
                                      reshard=reshard)
        self.param_store.publish(self.params, self.stage)

        # ---- overlap-aware adaptive N' -------------------------------
        # observe() runs on the consumer thread between stages; the
        # producer reads the plain-int target at collect start (GIL-atomic)
        self._concurrency_ctrl = (AdaptiveConcurrencyController(ro_cfg)
                                  if ro_cfg.adaptive_concurrency else None)
        self._concurrency_target: Optional[int] = (
            self._concurrency_ctrl.target if self._concurrency_ctrl else None)

        self._progress = threading.Condition()
        self._batches: "queue.Queue[_StageBatch]" = queue.Queue(
            maxsize=self.max_staleness + 1)
        self._producer: Optional[threading.Thread] = None
        self._producer_exc: Optional[BaseException] = None
        self._collect_idx = 0                 # next collect, producer-owned
        self._trained_batches = 0             # consumed collects
        # store totals already reported, so step metrics emit per-step
        # deltas (summable across a run like every sibling *_time field)
        ps_stats = self.param_store.stats_snapshot()
        self._reported_dropped = ps_stats["dropped"]
        self._reported_reshard_time = ps_stats["reshard_time"]
        self._stop = threading.Event()
        self._closed = False

    # ------------------------------------------------------------------
    # rollout production (caller thread when sequential, producer thread
    # when overlapped — never both in a given mode, but evaluate() splits
    # the key from the consumer while a producer may be mid-collect, so
    # the split-and-advance is guarded)
    # ------------------------------------------------------------------
    def _next_rollout_key(self):
        with self._progress:
            self.key, k = jax.random.split(self.key)
        return k

    def _collect_stage(self, params, version: int, idx: int) -> _StageBatch:
        k_roll = self._next_rollout_key()
        groups, roll_stats = self.engine.collect(
            params, version, k_roll,
            target_concurrency=self._concurrency_target)
        return _StageBatch(collect_idx=idx, params_version=version,
                           groups=groups, roll_stats=roll_stats)

    def _producer_loop(self):
        try:
            while not self._stop.is_set():
                # staleness gate: collect ``idx`` trains as the ``idx``-th
                # consumed batch, so its params snapshot may lag the
                # training stage by at most max_staleness updates
                with self._progress:
                    idx = self._collect_idx
                    while (self._trained_batches < idx - self.max_staleness
                           and not self._stop.is_set()):
                        self._progress.wait(timeout=0.1)
                if self._stop.is_set():
                    return
                # freshest published version (rollout layout when
                # disaggregated) — never a superseded one
                params, version = self.param_store.acquire()
                item = self._collect_stage(params, version, idx)
                with self._progress:
                    self._collect_idx = idx + 1
                while not self._stop.is_set():
                    try:
                        self._batches.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:           # surfaced by _next_batch
            self._producer_exc = e

    def _ensure_producer(self):
        if self._closed:
            raise RuntimeError("trainer is closed")
        if self._producer is None:
            self._producer = threading.Thread(target=self._producer_loop,
                                              name="copris-rollout",
                                              daemon=True)
            self._producer.start()

    def _next_batch(self) -> _StageBatch:
        deadline = (None if self.batch_timeout is None
                    else time.perf_counter() + self.batch_timeout)
        while True:
            try:
                return self._batches.get(timeout=0.2)
            except queue.Empty:
                pass
            if self._producer_exc is not None:
                raise RuntimeError("rollout producer failed") \
                    from self._producer_exc
            if self._producer is not None and not self._producer.is_alive():
                raise RuntimeError("rollout producer exited without a batch")
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError(
                    f"no rollout batch within {self.batch_timeout}s — "
                    "overlapped pipeline wedged?")

    # ------------------------------------------------------------------
    def step(self) -> dict:
        """One training step. Sequential mode collects inline; overlapped
        mode consumes the producer's next batch (collected under params up
        to ``max_staleness`` updates behind the ones being trained)."""
        t0 = time.perf_counter()
        if self.overlap:
            with Span("trainer.batch_wait"):
                self._ensure_producer()
                item = self._next_batch()
        else:
            # same handoff as the producer thread: freshest published
            # version — identical to (self.params, self.stage) here, since
            # the sequential consumer is the only publisher
            with Span("trainer.collect"):
                params, version = self.param_store.acquire()
                with self._progress:
                    idx = self._collect_idx
                item = self._collect_stage(params, version, idx)
                with self._progress:
                    self._collect_idx += 1
        t_collected = time.perf_counter()
        out = self._train_on(item, t0, t_collected)
        self.history.append(out)
        return out

    def train_inputs(self, batch: dict, stage: int):
        """The train step's device batch and learning rate for a packed
        batch (``pack_groups``) trained at ``stage``."""
        jb = {k: jnp.asarray(v) for k, v in batch.items()
              if k in ("tokens", "loss_mask", "behaviour_logp")}
        jb["advantages"] = grpo.group_advantages(
            jnp.asarray(batch["rewards"]), self.ro.group_size)
        lr = schedule.warmup_constant(jnp.asarray(stage, jnp.float32),
                                      lr=self.tcfg.lr,
                                      warmup_steps=self.tcfg.warmup_steps)
        return jb, lr

    def _train_on(self, item: _StageBatch, t0: float,
                  t_collected: float) -> dict:
        groups, roll_stats = item.groups, item.roll_stats
        # rewards were computed asynchronously during rollout (paper §5.1:
        # async rewards on both arms); gather resolves any stragglers and
        # runs on the CONSUMER thread, so the producer keeps submitting
        # stage k+1 rewards while stage k gathers
        with Span("trainer.reward"):
            self.reward_worker.gather(groups)
        t_reward = time.perf_counter()

        phases = {}
        train_stage = self.stage
        with Span("trainer.pack", phases, "pack_time"):
            batch = pack_groups(groups, max_len=self.engine.max_len)
            jb, lr = self.train_inputs(batch, train_stage)
            # the span covers the host->device copy, not its enqueue
            jax.block_until_ready((jb, lr))
        with Span("trainer.train", phases, "train_time"):
            new_params, new_opt, metrics = self._train_step(
                self.params, self.opt_state, jb, lr)
            # publish the update as a new version for the producer
            # (resharded to the rollout layout when disaggregated), then
            # wake its staleness gate. Only the consumer thread mutates
            # params/opt_state/stage; the producer reads exclusively through
            # the store, so no lock is needed around the plain assignments.
            self.params, self.opt_state = new_params, new_opt
            self.stage = train_stage + 1
            self.param_store.publish(new_params, self.stage)
            with self._progress:
                self._trained_batches += 1
                self._progress.notify_all()
            # jit dispatch is async: without forcing completion here, t_end
            # excludes the train compute (and, overlapped, its contention
            # with the producer's rollout on a shared device) —
            # step_time/update_time would under-report and
            # overlap_saved_time overstate. Publish happens BEFORE the block
            # so the producer's gate opens on the future-backed params as
            # early as possible.
            jax.block_until_ready((new_params, metrics))
        t_end = time.perf_counter()

        # staleness accounting relative to the CONSUMING training stage:
        # gap = train_stage - token's stage id (satellite fix — a partial
        # finished entirely under stage k-1 but trained at stage k counts
        # all its tokens as off-policy)
        stages_arr = batch["stage_ids"]
        resp = stages_arr >= 0
        n_resp = int(resp.sum())
        gaps = (train_stage - stages_arr)[resp]
        staleness_hist = {int(g): int(c) for g, c in
                          zip(*np.unique(gaps, return_counts=True))}
        off_tokens = int((gaps > 0).sum())

        out = {k: float(v) for k, v in metrics.items()}
        # ONE consistent counter snapshot for both the reported deltas and
        # the new reported totals — reading the live dict twice could lose
        # a concurrent publish's increment between the reads
        ps_stats = self.param_store.stats_snapshot()
        rollout_time = roll_stats["wall_time"]
        update_time = t_end - t_reward
        reward_time = self.reward_worker.last_gather_time
        step_time = t_end - t0

        # overlap-aware adaptive N': feed this stage's finish/refill
        # balance (rollout wall vs the consumer work it overlapped) to the
        # controller; the producer picks the new target up at its NEXT
        # collect start — concurrency adjusts between stages, never inside
        # one
        if self._concurrency_ctrl is not None:
            self._concurrency_target = self._concurrency_ctrl.observe(
                rollout_time=rollout_time,
                train_time=t_end - t_collected,
                evicted=roll_stats["evicted"])
        out.update(
            step=train_stage,
            reward_mean=float(batch["rewards"].mean()),
            reward_std=float(batch["rewards"].std()),
            rollout_time=rollout_time,
            # the rollout's phases (engine spans) and the update's
            prefill_time=roll_stats["prefill_time"],
            decode_time=roll_stats["decode_time"],
            replay_time=roll_stats["replay_time"],
            pack_time=phases["pack_time"],
            train_time=phases["train_time"],
            # the reward worker's own gather timing: time the trainer spent
            # blocked on reward resolution (subtracting rollout wall-time
            # from a different clock span could go negative)
            reward_time=reward_time,
            update_time=update_time,
            host_syncs=roll_stats["host_syncs"],
            tokens_per_sync=roll_stats["tokens_per_sync"],
            step_time=step_time,
            off_policy_frac=off_tokens / max(1, n_resp),
            staleness_hist=staleness_hist,
            # optimizer updates between the batch's rollout params and the
            # params trained on it: 0 sequentially, <= max_staleness overlapped
            param_staleness=train_stage - item.params_version,
            batch_wait_time=(t_collected - t0 if self.overlap else 0.0),
            # what the sequential pipeline would have paid on top of this
            # step's wall-clock (rollout ran concurrently with the previous
            # train step)
            overlap_saved_time=(max(0.0, rollout_time + reward_time
                                    + update_time - step_time)
                                if self.overlap else 0.0),
            multi_stage_trajs=roll_stats["multi_stage_trajs"],
            utilization=roll_stats["utilization"],
            buffer_unfinished=roll_stats["buffer_unfinished"],
            # the in-flight target the collect ran under (static N' unless
            # adaptive_concurrency) and the weight-sync channel state
            # (versions held is a gauge; dropped/reshard are THIS step's)
            concurrency_target=roll_stats["concurrency_target"],
            param_store_versions=self.param_store.num_versions,
            dropped_versions=(ps_stats["dropped"]
                              - self._reported_dropped),
            reshard_time=(ps_stats["reshard_time"]
                          - self._reported_reshard_time),
            mean_resp_len=float(np.mean([len(t.response_tokens)
                                         for g in groups
                                         for t in g.trajectories])),
            # multi-turn environment accounting (all 0 for single-turn)
            env_steps=roll_stats.get("env_steps", 0),
            env_turns=roll_stats.get("env_turns", 0),
            env_failures=roll_stats.get("env_failures", 0),
            env_wait_time=roll_stats.get("env_wait_time", 0.0),
            env_timeouts=(self.env_worker.stats_snapshot()["env_timeouts"]
                          if self.env_worker is not None else 0),
        )
        self._reported_dropped = ps_stats["dropped"]
        self._reported_reshard_time = ps_stats["reshard_time"]
        self.last_groups = groups
        self.last_batch = batch
        return out

    # ------------------------------------------------------------------
    def restore(self, *, params=None, opt_state=None, stage=None):
        """Resume from checkpoint state: update the trainer fields AND
        republish through the ParamStore so the rollout side acquires the
        restored weights (setting ``.params``/``.stage`` directly would
        leave the store serving the construction-time version). Must be
        called before the first ``step()``."""
        if self._producer is not None:
            raise RuntimeError("restore() after the producer started — "
                               "restore before the first step()")
        if params is not None:
            self.params = params
        if opt_state is not None:
            self.opt_state = opt_state
        if stage is not None:
            if stage < self.stage:
                raise ValueError(
                    f"restore to stage {stage} < current {self.stage}: "
                    "ParamStore versions are strictly monotonic — build a "
                    "fresh trainer to rewind")
            self.stage = stage
        self.param_store.publish(self.params, self.stage, replace=True)

    # ------------------------------------------------------------------
    def close(self):
        """Stop the producer thread and the reward pool. Idempotent; only
        needed for ``overlap=True`` but always safe to call."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        with self._progress:
            self._progress.notify_all()
        if self._producer is not None:
            # drain so a blocked put() observes the stop flag
            while self._producer.is_alive():
                try:
                    self._batches.get_nowait()
                except queue.Empty:
                    pass
                self._producer.join(timeout=0.2)
        self.reward_worker.shutdown()
        if self.env_worker is not None:
            self.env_worker.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    def evaluate(self, n_prompts: int = 32, *, key=None) -> float:
        """Greedy accuracy on fresh task prompts (exact reward)."""
        key = key if key is not None else jax.random.PRNGKey(123)
        eos_id = self.engine.eos_id    # the id rollout/training stopped on
        # evaluate is a rollout-side consumer: freshest published version
        # (rollout layout when disaggregated)
        params, _ = self.param_store.acquire()
        correct = 0.0
        for i in range(n_prompts):
            cache = M.init_cache(self.cfg, 1, self.engine.max_len)
            prompt, answer = self.safe_task.sample_prompt()
            L = len(prompt)
            pad = np.zeros(-(-L // 16) * 16, np.int32)
            pad[:L] = prompt
            logits, cache = M.prefill(params, self.cfg,
                                      jnp.asarray(pad)[None], jnp.asarray([L]),
                                      cache)
            toks, cl = [], L
            tok = int(jnp.argmax(logits[0]))
            for _ in range(32):
                toks.append(tok)
                if tok == eos_id:
                    break
                lg, cache = M.decode_step(params, self.cfg,
                                          jnp.asarray([tok]), cache,
                                          jnp.asarray([cl]))
                cl += 1
                tok = int(jnp.argmax(lg[0]))
            correct += self.task.reward(toks, answer)
        return correct / n_prompts
