"""Slot-pool rollout engine — Concurrency-Controlled Partial Rollout.

TPU-native continuous batching (DESIGN.md §3) with CHUNKED DEVICE-SIDE
DECODE: a fixed pool of ``N'`` slots, each slot owning a region of the
batched KV/state cache. Every engine step runs ONE jitted
``jax.lax.scan`` of ``decode_chunk`` decode+sample iterations over all N'
slots; EOS / max-length stops are detected on device, so the host touches
the device once per chunk — ``(tokens, logps, active)`` in a single
transfer — instead of once per token. The host then *replays* the chunk in
(step, slot) order: appending token runs to trajectories, trimming
post-stop / post-termination over-generation, and refilling freed slots
through ONE batched multi-slot prefill over a padded bucket (padding rows
carry an out-of-bounds slot id and are dropped by the scatter). Early
termination fires when B groups are complete; in-flight trajectories stay
in the buffer with their per-stage behaviour log-probs.

Sampling uses a **per-trajectory PRNG stream**: the key for response token
``j`` of trajectory ``(group_id, sample_idx)`` is::

    fold_in(fold_in(fold_in(stage_key, group_id), sample_idx), j)

so the sampled stream is a pure function of the trajectory identity — not
of slot assignment, batch composition, or chunk size. Any ``decode_chunk``
therefore yields bit-identical trajectory content; only *timing* differs
(refills land at chunk boundaries, so which trajectories early
termination cuts off, and the trimmed over-generation accounting, may
shift — measured in tests/test_rollout_chunked.py).

Modes: "copris" | "sync" (the veRL-style baseline) | "naive_partial"
(Kimi-K1.5-style one-shot over-generation).
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig, RolloutConfig
from repro.common.spans import Span
from repro.core.buffer import TrajectoryBuffer
from repro.core.scheduler import ConcurrencyScheduler
from repro.core.trajectory import Group, Trajectory
from repro.kernels import pallas_by_default
from repro.models import model as M
from repro.sampling import kv_cache as kvc
from repro.sampling import sampler

PREFILL_BUCKET = 64


def _round_up(n, m):
    return -(-n // m) * m


def prefill_pad_dims(lens, n_rows, n_pending):
    """Static jit signature of one batched prefill: (padded seq len S,
    padded row count nr, padded scatter count ns). Every raw batch inside
    one (bucket, pow2-rows, pow2-pending) cell MUST map to the same triple
    — this bounds compilation count at O(#buckets), and ``irlint`` IR401
    lowers its recompilation-hazard check on this exact function."""
    S = _round_up(max(lens), PREFILL_BUCKET)
    nr = 1 << (n_rows - 1).bit_length()
    ns = 1 << (n_pending - 1).bit_length()
    return S, nr, ns


def _fold_slot_keys(stage_key, gid, sidx):
    """(pool,) group ids + sample indices -> (pool, 2) per-trajectory keys."""
    k = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(stage_key, gid)
    return jax.vmap(jax.random.fold_in)(k, sidx)


def stop_flags(tok, resp_len_after, total_len_after, *, eos_id: int,
               max_response_len: int, max_len: int):
    """THE stop predicate — one definition shared by the device sampler
    (`_sample_step`) and the host replay (`_maybe_done`), so the two sides
    cannot drift apart and trip the desync assert.

    Evaluated on *post-append* quantities: ``resp_len_after`` /
    ``total_len_after`` count the token ``tok`` that just landed. The
    total-length bound stops at ``max_len - 1`` so the next decode step never
    writes K/V past cache capacity. Works elementwise on jnp arrays (device)
    and on python ints (host).

    Returns ``(eos_stop, length_stop)`` — the host reports EOS with
    priority when both fire on the same token.
    """
    eos = tok == eos_id
    length = ((resp_len_after >= max_response_len)
              | (total_len_after >= max_len - 1))
    return eos, length


class RolloutEngine:
    def __init__(self, model_cfg: ModelConfig, ro_cfg: RolloutConfig,
                 prompt_source: Callable[[], Tuple[np.ndarray, object]], *,
                 eos_id: int, media=None, use_pallas: Optional[bool] = None,
                 max_len: Optional[int] = None,
                 on_finish: Optional[Callable] = None,
                 env_factory: Optional[Callable] = None,
                 env_worker=None):
        self.cfg = model_cfg
        self.ro = ro_cfg
        self.prompt_source = prompt_source
        self.eos_id = eos_id
        self.media = media
        if use_pallas is None:
            use_pallas = pallas_by_default()
        self.use_pallas = use_pallas

        self.on_finish = on_finish      # async-reward hook: (traj, answer)
        self._answers = {}
        # ---- multi-turn environments -----------------------------------
        # env_factory(spec) -> Environment (spec = the prompt source's
        # answer slot). When set, every EOS/length stop yields the slot and
        # hands the finished turn to the AsyncEnvWorker; observations are
        # integrated (and the trajectory re-prefilled) at chunk boundaries.
        # None preserves the single-turn path bit-exactly.
        self.env_factory = env_factory
        self.env_worker = env_worker
        if env_factory is not None and env_worker is None:
            from repro.core.reward_worker import AsyncEnvWorker
            self.env_worker = AsyncEnvWorker(
                timeout=ro_cfg.env_step_timeout or None)
        self._env_pending = {}          # traj_id -> parked Trajectory
        # the slot pool is a fixed jit shape: under adaptive N' it is sized
        # to the controller's upper bound so a between-stage target change
        # never needs a recompile — stages running below the bound simply
        # leave slots idle
        self.pool = ro_cfg.slot_pool
        self.max_len = max_len or _round_up(
            ro_cfg.max_prompt_len + ro_cfg.max_response_len, PREFILL_BUCKET)
        self._chunk = ro_cfg.decode_chunk

        self.buffer = TrajectoryBuffer()
        # the cache lives behind a CacheBackend: "dense" is the historical
        # one-region-per-slot layout, "paged" shares physical page pools
        # across slots with block-table indirection (admission then gates on
        # free PAGES, not free slots — continuous batching)
        self.backend = kvc.make_backend(
            ro_cfg.kv_backend, model_cfg, self.pool, self.max_len,
            page_size=ro_cfg.kv_page_size, num_pages=ro_cfg.kv_num_pages)
        # pages promised to dispatched-but-not-yet-prefilled work
        self._reserved_pages = 0
        self._reservations = {}        # traj_id -> reserved page count
        self.cache_len = np.zeros(self.pool, np.int32)
        self.last_token = np.zeros(self.pool, np.int32)
        self.slot_gid = np.zeros(self.pool, np.int32)   # key-stream identity
        self.slot_sidx = np.zeros(self.pool, np.int32)
        self.slots: List[Optional[Trajectory]] = [None] * self.pool
        self._group_counter = 0
        self.stats_total = {}
        # guards stats_total: _end_stage accumulates on whichever thread
        # drives the stage (the overlapped trainer's producer), while
        # consumer-side code reads totals via stats_snapshot()
        self._stats_lock = threading.Lock()
        # the engine OWNS its donated KV cache: _decode_chunk/_prefill_batch
        # donate it, so a second concurrent collect would consume a buffer
        # the first one already invalidated. The overlapped trainer drives
        # collect from a single producer thread; this guard turns any
        # accidental re-entry into a loud error instead of a use-after-free.
        self._collect_guard = threading.Lock()

        # ---- jitted engine steps -------------------------------------
        is_paged = self.backend.is_paged          # static: baked into jits
        page_size = ro_cfg.kv_page_size

        # One sampler for both prefill and decode. Above the Pallas gate the
        # fused top-k/top-p kernel (kernels/fused_sample) draws tokens
        # without materialising a full-vocab softmax/sort per step; it
        # regenerates the same threefry Gumbel bits, so token streams stay
        # bit-identical to the XLA sampler (and chunk-size invariant).
        if use_pallas:
            from repro.kernels.fused_sample import ops as fs_ops
            _sample_rows = functools.partial(
                fs_ops.fused_sample_rows, temperature=ro_cfg.temperature,
                top_p=ro_cfg.top_p, top_k=ro_cfg.top_k)
        else:
            _sample_rows = functools.partial(
                sampler.sample_rows, temperature=ro_cfg.temperature,
                top_p=ro_cfg.top_p, top_k=ro_cfg.top_k)

        def _sample_step(logits, cache_len, active, aux):
            """Device-side sample + stop detection via the SAME predicate as
            the host's _maybe_done (`stop_flags`). Slot invariant entering a
            step: cache_len == prompt + resp_len - 1, so after this token
            lands resp == resp_len+1 and total == cache_len + 2."""
            resp_len, slot_keys = aux
            keys = jax.vmap(jax.random.fold_in)(slot_keys, resp_len)
            tok, logp = _sample_rows(keys, logits)
            resp_new = resp_len + active.astype(jnp.int32)
            eos, length = stop_flags(
                tok, resp_new, cache_len + 2, eos_id=eos_id,
                max_response_len=ro_cfg.max_response_len,
                max_len=self.max_len)
            return tok, logp, eos | length, (resp_new, slot_keys)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _decode_chunk(params, cache, last_token, cache_len, active,
                          resp_len, gid, sidx, stage_key, block_table):
            # block_table is a jit ARGUMENT (never a closure — a closed-over
            # jnp array would bake into the executable as a constant); dense
            # mode passes a (1, 1) dummy so both backends share one signature
            slot_keys = _fold_slot_keys(stage_key, gid, sidx)
            (cache, *_), ys = M.decode_scan(
                params, model_cfg, cache, last_token, cache_len, active,
                (resp_len, slot_keys), steps=self._chunk,
                step_fn=_sample_step, media=self._media_for(self.pool),
                use_pallas=use_pallas,
                paged=(block_table, page_size) if is_paged else None)
            return cache, ys

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill_batch(params, cache, tokens, lengths, slot_ids, row_map,
                           flat_pos, gid, sidx, resp_idx, stage_key):
            # scratch is sized to the prompt bucket S, not max_len — a
            # whole-pool initial fill must not transiently double the
            # pool cache; the backend insert writes the S-long prefix.
            # tokens holds one row per UNIQUE prompt; row_map maps each
            # output sample to its row (identity for dense — prefix sharing
            # lets a whole GRPO group ride on one prefill row)
            n, S = tokens.shape
            scratch = M.init_cache(model_cfg, n, S)
            logits, scratch = M.prefill(params, model_cfg, tokens, lengths,
                                        scratch, media=self._media_for(n),
                                        use_pallas=use_pallas)
            logits = jnp.take(logits, row_map, axis=0, mode="clip")
            keys = jax.vmap(jax.random.fold_in)(
                _fold_slot_keys(stage_key, gid, sidx), resp_idx)
            tok, logp = _sample_rows(keys, logits)
            if is_paged:
                cache = kvc.paged_insert_rows(cache, scratch, slot_ids,
                                              row_map, flat_pos)
            else:
                cache = kvc.dense_insert_rows(cache, scratch, slot_ids,
                                              row_map)
            return tok, logp, cache

        self._decode_chunk_fn = _decode_chunk
        self._prefill_batch_fn = _prefill_batch

    # ------------------------------------------------------------------
    @property
    def cache(self):
        """Device cache pytree, owned by the backend (donated by the jitted
        engine steps and reassigned after every call)."""
        return self.backend.cache

    @cache.setter
    def cache(self, value):
        self.backend.cache = value

    def stats_snapshot(self) -> dict:
        """Consistent copy of the lifetime stat totals. Cross-thread
        readers (the consumer, while the producer collects) use this
        instead of reaching into ``stats_total``."""
        with self._stats_lock:
            return dict(self.stats_total)

    # ------------------------------------------------------------------
    def _media_for(self, batch):
        if self.media is None:
            return None
        m = jnp.asarray(self.media)
        return jnp.broadcast_to(m[None], (batch,) + m.shape)

    def _new_group(self) -> Optional[Group]:
        # a prompt source may return None to DECLINE (finite workloads: a
        # serving queue that is currently empty) — the scheduler then leaves
        # the slot idle instead of opening a group with no prompt
        src = self.prompt_source()
        if src is None:
            return None
        prompt, answer = src
        g = Group(group_id=self._group_counter, prompt_tokens=np.asarray(prompt, np.int32),
                  answer=answer, size=self.ro.group_size)
        self._answers[g.group_id] = answer
        self._group_counter += 1
        return g

    def _finish(self, traj: Trajectory, reason: str, sched: ConcurrencyScheduler):
        traj.done = True
        traj.finish_reason = reason
        if self.on_finish is not None:      # async reward pipeline
            self.on_finish(traj, self._answers.get(traj.group_id))
        sched.release(traj)

    def _stop_slot(self, traj: Trajectory, reason: str,
                   sched: ConcurrencyScheduler):
        """A slot-resident trajectory hit a stop (EOS / length). Single-turn:
        the episode is over. Multi-turn: the TURN is over — yield the slot
        (the caller frees it, returning its pages to continuous-batching
        admission) and hand the turn to the async environment."""
        if self.env_factory is None:
            self._finish(traj, reason, sched)
            return
        if traj.env is None:
            traj.env = self.env_factory(self._answers.get(traj.group_id))
            traj.env.reset()
        traj.awaiting_env = True
        # a length stop means the response budget is exhausted: the pending
        # env step is the episode's last (reward still counts, observation
        # is discarded — there is no room to decode another turn)
        traj.env_final = traj.env_final or reason == "length"
        sched.release(traj)
        self._env_pending[traj.traj_id] = traj
        self.env_worker.submit(traj.traj_id, traj.env.step,
                               traj.turn_tokens())
        self._stats["env_steps"] += 1

    def _finish_episode(self, traj: Trajectory, sched: ConcurrencyScheduler):
        """Close a multi-turn episode: the env-accumulated return IS the
        reward (no on_finish — the reward worker has nothing to score)."""
        traj.awaiting_env = False
        traj.done = True
        traj.finish_reason = "length" if traj.env_final else "env_done"
        traj.reward = float(traj.env_return)
        sched.release(traj)

    def _poll_env(self, sched: ConcurrencyScheduler, *, block: bool = False):
        """Integrate finished environment steps (engine thread only): append
        observations and return trajectories to the dispatch pool, or close
        episodes the env declared done. Timeouts / raising env fns end the
        episode with the reward accumulated so far — never a wedged stage."""
        if not self._env_pending:
            return
        if block:
            t0 = time.perf_counter()
            self.env_worker.wait(0.05)
            self._stats["env_wait_time"] += time.perf_counter() - t0
        finished = False
        for key, ok, val in self.env_worker.poll():
            traj = self._env_pending.pop(key, None)
            if traj is None:
                continue
            traj.awaiting_env = False
            if not ok:
                self._stats["env_failures"] += 1
                traj.env_final = True
                obs, done = np.empty(0, np.int32), True
            else:
                obs, r, done = val
                obs = np.asarray(obs, np.int32).reshape(-1)
                traj.env_return += float(r)
            if not done and not traj.env_final:
                # room check: the next turn needs the observation plus at
                # least one decodable model token inside both length budgets
                if (traj.response_len + len(obs) >= self.ro.max_response_len
                        or traj.total_len + len(obs) >= self.max_len - 1):
                    traj.env_final = True
                else:
                    traj.append_env(obs, self._stage)
                    self._stats["env_turns"] += 1
                    continue           # resumable: next dispatch re-prefills
            self._finish_episode(traj, sched)
            finished = True
        if finished:
            sched.harvest()

    def _maybe_done(self, traj: Trajectory) -> Optional[str]:
        if not traj.response_tokens:
            return None
        eos, length = stop_flags(
            traj.response_tokens[-1], traj.response_len, traj.total_len,
            eos_id=self.eos_id, max_response_len=self.ro.max_response_len,
            max_len=self.max_len)
        # an environment observation can legally contain the EOS id; only a
        # MODEL-sampled EOS ends a turn (device decode only ever samples
        # model tokens, so the device/host stop predicates stay in lockstep)
        if eos and traj.roles[-1] == 1:
            return "eos"
        if length:
            return "length"
        return None

    # -- slot refill ---------------------------------------------------
    def _resume_snapshot(self, i: int, traj: Trajectory):
        """resume_strategy="kv_snapshot": restore the evicted slot state
        verbatim — no re-prefill cost, but after a policy update the
        continuation attends to STALE K/V, so the effective behaviour
        distribution is not any single policy's (bias/throughput tradeoff
        the paper avoids by buffering tokens, not KV; measured in
        tests/test_kv_snapshot.py). Routed through the backend: dense
        snapshots are per-slot cache slices, paged snapshots are page LISTS
        (scattered back into freshly allocated physical pages — never
        densified)."""
        self.backend.insert_snapshot(traj.kv_snapshot, i)
        self.slots[i] = traj
        self.cache_len[i] = traj.snap_cache_len
        self.last_token[i] = traj.snap_last_token
        self.slot_gid[i] = traj.group_id
        self.slot_sidx[i] = traj.sample_idx
        traj.kv_snapshot = None
        self._stats["resumed"] += 1
        self._stats["snapshot_resumes"] = \
            self._stats.get("snapshot_resumes", 0) + 1

    def _admission_cost(self, traj: Trajectory, fresh_gids: set) -> int:
        """Worst-case free pages this admission needs (paged backend):
        snapshot restores bill their exact page count; prefills bill pages
        through the first decode chunk; a fresh spawn whose group primary is
        already admitted only bills pages past the shared full prompt
        pages."""
        if (self.ro.resume_strategy == "kv_snapshot"
                and traj.kv_snapshot is not None):
            return self.backend.snapshot_pages(traj.kv_snapshot)
        shared = (self.ro.kv_prefix_sharing and traj.response_len == 0
                  and traj.group_id in fresh_gids)
        return self.backend.admission_pages(traj.total_len,
                                            lookahead=self._chunk,
                                            shared=shared)

    def _dispatch_refills(self, idxs, sched: ConcurrencyScheduler):
        """Decide what fills freed slots, in slot order (one sequential
        scheduler dispatch per slot, so scheduling policy is invariant to
        the decode chunk size). kv_snapshot resumes are restored in place
        (device scatter, no host sync); re-prefill trajectories are
        returned as (slot, traj) pairs for the batched prefill.

        Paged backend: admission is additionally gated on free PAGES —
        continuous batching. A dispatch the page budget cannot cover is
        handed back to the scheduler (requeue, redispatched with priority)
        and the remaining freed slots stay idle this round; they are
        re-offered at the next chunk boundary, when decode/finishes may
        have freed pages."""
        pending: List[Tuple[int, Trajectory]] = []
        queue = list(idxs)
        paged = self.backend.is_paged
        if paged:
            budget = self.backend.free_page_count() - self._reserved_pages
            fresh_gids = set()         # groups with an admitted fresh spawn
        while queue and not sched.done:
            batch = sched.next_requests(len(queue))
            exhausted = len(batch) < len(queue)
            redo = []
            blocked = False
            for bi, (i, traj) in enumerate(zip(queue, batch)):
                if paged:
                    cost = self._admission_cost(traj, fresh_gids)
                    if cost > budget:
                        # hand this and every later dispatch of the batch
                        # back — scheduler order is priority order
                        for t2 in batch[bi:]:
                            sched.requeue(t2)
                        self._stats["admission_blocked"] += \
                            len(batch) - bi
                        blocked = True
                        break
                    budget -= cost
                if (self.ro.resume_strategy == "kv_snapshot"
                        and traj.kv_snapshot is not None):
                    self._resume_snapshot(i, traj)   # allocates pages now
                    reason = self._maybe_done(traj)
                    if reason is not None:
                        self._stop_slot(traj, reason, sched)
                        self.slots[i] = None
                        self.backend.free_slot(i)
                        sched.harvest()
                        redo.append(i)
                else:
                    if paged:
                        self._reserved_pages += cost
                        self._reservations[traj.traj_id] = cost
                        if traj.response_len == 0:
                            fresh_gids.add(traj.group_id)
                    pending.append((i, traj))
            queue = redo
            if exhausted or blocked:
                break
        return pending

    def _prefill_pending(self, pending, params, stage_key):
        """ONE batched prefill over all freed slots: rows padded to a
        common PREFILL_BUCKET length, row count padded to a power of two
        (padding rows scatter to the out-of-bounds slot id ``pool`` and
        are dropped). Returns the rows that finished immediately (their
        very first sampled token already ended the trajectory).

        Prefix sharing (paged backend): fresh same-group spawns collapse
        onto ONE prefill row — the first ("primary") slot allocates and
        fills the prompt pages, the other G-1 members just point their
        block tables at them (refcounted; copy-on-write restores
        exclusivity on the first divergent write). Each member still
        samples its own first token from the shared row's logits under its
        own PRNG stream, so trajectory content is unchanged."""
        fulls = [t.full_tokens() for _, t in pending]
        lens = [len(f) for f in fulls]
        for L in lens:
            assert L < self.max_len, \
                f"trajectory length {L} >= max_len {self.max_len}"
        paged = self.backend.is_paged
        if paged:
            for _, traj in pending:
                self._reserved_pages -= self._reservations.pop(
                    traj.traj_id, 0)
        share = self.backend.supports_sharing and self.ro.kv_prefix_sharing
        # row assignment: one row per unique prefill
        rows = []                      # (full_tokens, L, primary_slot)
        row_of_gid = {}
        row_map, primary = [], []
        for (i, traj), f, L in zip(pending, fulls, lens):
            fresh = traj.response_len == 0
            if not fresh:
                # a partial resumed by re-prefill: its prompt and every
                # response token so far go through the model again
                self._stats["reprefill_tokens"] += L
            if share and fresh and traj.group_id in row_of_gid:
                row_map.append(row_of_gid[traj.group_id])
                primary.append(False)
            else:
                r = len(rows)
                rows.append((f, L, i))
                if share and fresh:
                    row_of_gid[traj.group_id] = r
                row_map.append(r)
                primary.append(True)
        S, nr, ns = prefill_pad_dims(lens, len(rows), len(pending))
        tokens = np.zeros((nr, S), np.int32)
        lengths = np.ones(nr, np.int32)
        if paged:
            oob = self.backend.num_pages * self.backend.page_size
            flat_pos = np.full((nr, S), oob, np.int32)  # OOB -> dropped
        else:
            flat_pos = np.zeros((1, 1), np.int32)       # unused dummy
        for r, (f, L, islot) in enumerate(rows):
            tokens[r, :L] = f
            lengths[r] = L
            if paged:
                flat_pos[r, :L] = self.backend.alloc_slot_prefix(islot, L)
            self._stats["prefill_tokens"] += L
        slot_ids = np.full(ns, self.pool, np.int32)   # OOB rows -> dropped
        rmap = np.zeros(ns, np.int32)
        gid = np.zeros(ns, np.int32)
        sidx = np.zeros(ns, np.int32)
        resp_idx = np.zeros(ns, np.int32)
        for s, ((i, traj), r, prim) in enumerate(
                zip(pending, row_map, primary)):
            slot_ids[s] = i
            rmap[s] = r
            gid[s] = traj.group_id
            sidx[s] = traj.sample_idx
            resp_idx[s] = traj.response_len
            if paged and not prim:
                self.backend.share_slots(rows[r][2], i, rows[r][1])
                self._stats["shared_prefill_rows"] += 1
        tok, logp, self.cache = self._prefill_batch_fn(
            params, self.cache, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(slot_ids), jnp.asarray(rmap), jnp.asarray(flat_pos),
            jnp.asarray(gid), jnp.asarray(sidx), jnp.asarray(resp_idx),
            stage_key)
        tok, logp = jax.device_get((tok, logp))
        self._stats["prefill_rows"] += len(rows)
        self._stats["host_syncs"] += 1
        finished = []
        for s, (i, traj) in enumerate(pending):
            traj.append(int(tok[s]), float(logp[s]), self._stage)
            self.slots[i] = traj
            self.cache_len[i] = lens[s]
            self.last_token[i] = int(tok[s])
            self.slot_gid[i] = traj.group_id
            self.slot_sidx[i] = traj.sample_idx
            self._stats["prefill_count"] += 1
            if traj.resume_count > 0 and traj.response_len > 1:
                self._stats["resumed"] += 1
            reason = self._maybe_done(traj)
            if reason:
                finished.append((i, traj, reason))
        return finished

    def _prefill_rounds(self, pending, sched: ConcurrencyScheduler, params,
                        stage_key):
        """Batched prefill, iterated: a prefill's very first sampled token
        may already be EOS, freeing the slot again. Dispatched work is
        prefilled even if early termination fired mid-chunk — the step-wise
        engine prefills at dispatch time, so these become 1-token partials
        that eviction buffers for prioritized resumption (rather than
        silently un-dispatching them)."""
        if not pending:
            return
        with Span("engine.prefill", self._stats, "prefill_time"):
            while pending:
                finished = self._prefill_pending(pending, params, stage_key)
                freed = []
                for i, traj, reason in finished:
                    self._stop_slot(traj, reason, sched)
                    self.slots[i] = None
                    self.backend.free_slot(i)
                    freed.append(i)
                pending = []
                if freed:
                    sched.harvest()
                    pending = self._dispatch_refills(freed, sched)

    def _preempt_slot(self, i: int, sched: ConcurrencyScheduler,
                      copies: Optional[List[Tuple[int, int]]] = None):
        """Evict a live slot mid-stage to free its pages. The trajectory
        keeps everything generated so far and goes back to the scheduler
        with redispatch priority (requeue) — under kv_snapshot resume it
        also carries its page-list snapshot, so preemption costs one
        re-prefill at worst and nothing at best.

        ``copies`` is the current round's pending COW batch: if the victim
        COW'd earlier in this round, its block table already points at copy
        DESTINATION pages whose scatter has not landed yet, so the batch
        must be flushed before a snapshot is extracted (sources are still
        intact — no decode write happens until after the round)."""
        traj = self.slots[i]
        if self.ro.resume_strategy == "kv_snapshot":
            if copies:
                self.backend.apply_copies(copies)
                copies.clear()
            traj.kv_snapshot = self.backend.extract_snapshot(i)
            traj.snap_cache_len = int(self.cache_len[i])
            traj.snap_last_token = int(self.last_token[i])
        sched.requeue(traj)
        self.slots[i] = None
        self.backend.free_slot(i)
        self._stats["page_preemptions"] += 1

    def _prepare_decode_pages(self, live, sched: ConcurrencyScheduler):
        """Before each decode chunk (paged backend only): ensure every live
        slot has pages mapped for the chunk's write range [cache_len,
        cache_len + chunk) and owns them EXCLUSIVELY (copy-on-write detaches
        prefix-shared pages on their first divergent write). On page
        exhaustion, preempt the youngest live slot (fewest response tokens —
        least redone work) until growth fits. Page copies are batched into
        one device scatter."""
        copies = []
        for i in range(self.pool):
            if not live[i]:
                continue
            clen = int(self.cache_len[i])
            upto = min(clen + self._chunk, self.max_len)
            while not self.backend.grow(i, upto, clen, copies):
                victim = None
                for j in range(self.pool):
                    if live[j] and j != i and (
                            victim is None or self.slots[j].response_len
                            < self.slots[victim].response_len):
                        victim = j
                if victim is None:
                    raise kvc.PageExhausted(
                        f"slot {i} cannot map its decode range [{clen}, "
                        f"{upto}) and no other live slot is preemptible — "
                        "kv_num_pages is too small for a single trajectory")
                self._preempt_slot(victim, sched, copies)
                live[victim] = False
                # drop pending COW copies targeting pages the preemption
                # just freed (their dst could be recycled to a new owner
                # before the batched copy lands); under kv_snapshot the
                # batch was already flushed and cleared before snapshotting
                copies[:] = [(s, d) for s, d in copies
                             if self.backend.refcount[d] > 0]
        self.backend.apply_copies(copies)
        return live

    # ------------------------------------------------------------------
    def collect(self, params, stage_id: int, key, *,
                target_concurrency: Optional[int] = None
                ) -> Tuple[List[Group], dict]:
        """Run rollout until B complete groups are collected (early
        termination). Returns (groups, stats).

        ``params`` is treated as an immutable snapshot: it is never donated
        (only the engine-owned cache is), so the caller may keep training on
        a newer params tree concurrently. ``collect`` itself is single-owner
        — it must only ever run on one thread at a time (see
        ``_collect_guard``).

        ``target_concurrency``: this stage's in-flight cap (adaptive N' —
        must not exceed the slot pool; None = the static configured N')."""
        self.begin_stage(params, stage_id, key,
                         target_concurrency=target_concurrency)
        try:
            while not self._sched.done and self.step_stage(params, key):
                pass
        except BaseException:
            self._collect_guard.release()
            raise
        return self.end_stage()

    # -- incremental stage API -----------------------------------------
    # collect() == begin_stage + step_stage-until-idle + end_stage. The
    # split exists so external drivers (launch/serve.py's ServeEngine) can
    # interleave their own work — admitting new requests, streaming partial
    # tokens — between decode chunks without owning the loop.

    def begin_stage(self, params, stage_id: int, key, *,
                    target_concurrency: Optional[int] = None
                    ) -> ConcurrencyScheduler:
        """Open a stage: reset per-stage stats, build the scheduler, and run
        the initial whole-pool fill. Takes the engine's single-owner guard
        (released by :meth:`end_stage`)."""
        if not self._collect_guard.acquire(blocking=False):
            raise RuntimeError(
                "RolloutEngine stage re-entered: the engine owns its "
                "donated KV cache and must be driven from a single thread")
        if target_concurrency is not None and not (
                1 <= target_concurrency <= self.pool):
            self._collect_guard.release()
            raise ValueError(
                f"target_concurrency {target_concurrency} outside "
                f"[1, pool={self.pool}] — the slot pool is sized to "
                "concurrency_max at engine construction")
        self._stage = stage_id
        self._stats = dict(prefill_count=0, prefill_tokens=0,
                           reprefill_tokens=0, prefill_rows=0,
                           shared_prefill_rows=0,
                           decode_steps=0, decode_chunks=0, host_syncs=0,
                           active_slot_steps=0, slot_steps=0, generated=0,
                           overgen_tokens=0, resumed=0, evicted=0,
                           admission_blocked=0, page_preemptions=0,
                           env_steps=0, env_turns=0, env_failures=0,
                           env_wait_time=0.0, prefill_time=0.0,
                           decode_time=0.0, replay_time=0.0)
        self._reserved_pages = 0
        self._reservations.clear()
        self._t0 = time.perf_counter()
        self._sched = ConcurrencyScheduler(
            self.ro, self.buffer, self._new_group,
            target_concurrency=target_concurrency)
        if self.ro.mode == "sync":
            assert len(self.buffer) == 0, "sync mode must start with empty buffer"

        # initial fill: one batched prefill over the whole pool
        self._prefill_rounds(
            self._dispatch_refills(range(self.pool), self._sched),
            self._sched, params, key)
        return self._sched

    def step_stage(self, params, key, *,
                   admit_idle: Optional[bool] = None) -> bool:
        """Run ONE decode chunk (+ its host replay and refill prefills).
        Returns False when the engine is idle — nothing live in the pool —
        so a bare ``while step_stage(...)`` loop terminates. ``admit_idle``
        re-offers idle slots to the scheduler before decoding (default: on
        for the paged backend, whose admission gate / preemption can idle
        slots mid-stage; serving drivers pass True so requests submitted
        between steps are admitted immediately)."""
        sched = self._sched
        stage_id = self._stage
        # integrate environment observations FIRST: returned trajectories
        # become resumable before this round's idle slots are re-offered
        self._poll_env(sched)
        has_env = self.env_factory is not None
        admit = ((self.backend.is_paged or has_env)
                 if admit_idle is None else admit_idle)
        if admit and not sched.done:
            # continuous batching: slots idled by an admission block, a page
            # preemption, an empty request queue, or an env-yielded turn are
            # re-offered every chunk boundary — finishes may have freed
            # pages / observations may have landed
            idle = [i for i in range(self.pool) if self.slots[i] is None]
            if idle:
                self._prefill_rounds(
                    self._dispatch_refills(idle, sched), sched, params, key)
        live = np.array([t is not None for t in self.slots], bool)
        if not live.any():
            if self._env_pending and not sched.done:
                # every in-flight trajectory is parked on its environment:
                # block briefly for an observation instead of spinning (the
                # worker's per-submit timeout bounds the total wait)
                self._poll_env(sched, block=True)
                return True
            return False               # nothing in flight and scheduler idle
        if self.backend.is_paged:
            live = self._prepare_decode_pages(live, sched)
            if not live.any():
                return True            # all preempted; retry next step
        D = self._chunk
        resp_len = np.array([0 if t is None else t.response_len
                             for t in self.slots], np.int32)
        with Span("engine.decode", self._stats, "decode_time"):
            self.cache, ys = self._decode_chunk_fn(
                params, self.cache, jnp.asarray(self.last_token),
                jnp.asarray(self.cache_len), jnp.asarray(live),
                jnp.asarray(resp_len), jnp.asarray(self.slot_gid),
                jnp.asarray(self.slot_sidx), key,
                self.backend.block_table_device())
            toks, logps, was_active = jax.device_get(ys)   # ONE transfer
        self._stats["decode_chunks"] += 1
        self._stats["host_syncs"] += 1
        self._stats["decode_steps"] += D
        self._stats["slot_steps"] += D * self.pool

        # host replay of the chunk, in (step, slot) order
        pending = []
        with Span("engine.replay", self._stats, "replay_time"):
            for d in range(D):
                if sched.done or not live.any():
                    self._stats["overgen_tokens"] += int(was_active[d:].sum())
                    break
                assert np.array_equal(was_active[d], live), \
                    "device/host stop detection desynchronised"
                step_live = np.nonzero(live)[0]
                self._stats["active_slot_steps"] += len(step_live)
                freed = []
                for i in step_live:
                    i = int(i)
                    traj = self.slots[i]
                    self.cache_len[i] += 1
                    tok = int(toks[d, i])
                    traj.append(tok, float(logps[d, i]), stage_id)
                    self.last_token[i] = tok
                    self._stats["generated"] += 1
                    reason = self._maybe_done(traj)
                    if reason:
                        self._stop_slot(traj, reason, sched)
                        self.slots[i] = None
                        self.backend.free_slot(i)
                        live[i] = False
                        freed.append(i)
                if freed:
                    sched.harvest()
                    pending.extend(self._dispatch_refills(freed, sched))
        self._prefill_rounds(pending, sched, params, key)
        return True

    def end_stage(self) -> Tuple[List[Group], dict]:
        """Close the stage: evict in-flight work to the buffer, finalize
        stats, release the single-owner guard."""
        try:
            return self._end_stage()
        finally:
            self._collect_guard.release()

    def _end_stage(self) -> Tuple[List[Group], dict]:
        sched = self._sched
        stage_id = self._stage
        t0 = self._t0
        st = self._stats
        with Span("engine.end_stage"):
            # early termination: evict in-flight work back to the buffer
            for i, traj in enumerate(self.slots):
                if traj is not None:
                    if self.ro.resume_strategy == "kv_snapshot":
                        traj.kv_snapshot = self.backend.extract_snapshot(i)
                        traj.snap_cache_len = int(self.cache_len[i])
                        traj.snap_last_token = int(self.last_token[i])
                    sched.release(traj)
                    self.slots[i] = None
                    self.backend.free_slot(i)
                    self._stats["evicted"] += 1
            sched.harvest()

            groups = sched.completed[: self.ro.batch_size]
            # surplus complete groups stay buffered for the next step
            for g in sched.completed[self.ro.batch_size:]:
                self.buffer.add_group(g)

            # the last decode chunk's cache update may still be dispatching —
            # force completion so wall_time covers compute, not enqueueing
            jax.block_until_ready(self.cache)
        st["wall_time"] = time.perf_counter() - t0
        st["concurrency_target"] = sched.target_concurrency
        st["buffer_unfinished"] = self.buffer.num_unfinished
        # how stale the carried-over buffer already is for the NEXT stage —
        # the overlapped pipeline's leading indicator of IS-correction load
        st["buffer_off_policy_frac"] = \
            self.buffer.off_policy_token_fraction(stage_id + 1)
        st["utilization"] = (st["active_slot_steps"] / st["slot_steps"]
                             if st["slot_steps"] else 1.0)
        st["tokens_per_sync"] = st["generated"] / max(1, st["host_syncs"])
        # off-policy accounting relative to THIS collect's stage (the stage
        # about to consume the batch), plus a per-stage-gap histogram —
        # {gap: token count} where gap = stage_id - token's stage. Under the
        # overlapped trainer the training stage may be ahead of stage_id;
        # the trainer re-derives its histogram against the train stage.
        all_stages = [np.asarray(t.stage_ids, np.int32)
                      for g in groups for t in g.trajectories]
        gaps, counts = np.unique(
            stage_id - np.concatenate(all_stages) if all_stages
            else np.empty(0, np.int32), return_counts=True)
        st["stage_gap_hist"] = {int(g_): int(c) for g_, c in zip(gaps, counts)}
        st["off_policy_tokens"] = int(counts[gaps > 0].sum())
        st["multi_stage_trajs"] = sum(1 for g in groups for t in g.trajectories
                                      if t.num_stages > 1)
        with self._stats_lock:
            for k_, v in st.items():
                if isinstance(v, (int, float)):
                    self.stats_total[k_] = self.stats_total.get(k_, 0) + v
        return groups, st
