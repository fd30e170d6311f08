#!/usr/bin/env python3
"""RL step throughput of the CoPRIS trainer, one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the chips the cell names; it exits non-zero with no result
line when JAX finds no TPU or too few chips. It builds the cell's trainer
(``CoPRISTrainer``, sequential, copris mode, dense cache) from the cell's
configuration and traffic files with weights made from the seed, drives it
through three steps that the reference later follows, warms every program
shape the window can meet, then runs ``step()`` until ``--seconds`` have
passed (the window closes at the end of the first step that ends after
them). ``--trace 1`` traces the window's first steps and reports the
per-layer metrics instead of the end-to-end ones.

Everything that depends on the model goes through the family module the
configuration file names (``bench/families/<family>.py``). After the
window the trainer is freed and the family's plain float32 reference
follows the three set-up steps; ``correct`` says
whether each compared number is within its limit
(``bench/limits/<cell>.json``). The last stdout line is the result object.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHECK_STEPS = 3      # set-up steps the reference follows
TRACE_STEPS = 2      # window steps inside the profiler trace
STEP_LOG = ("step_time", "rollout_time", "update_time", "prefill_time",
            "decode_time", "replay_time", "pack_time", "pg_loss",
            "ratio_mean", "off_policy_frac", "mean_resp_len")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, flush=True)


class CompileCounter:
    """Counts programs lowered (a jit cache miss: traced and compiled, or
    loaded from the persistent cache) while ``on``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.on, self.n, self.secs, self.names = False, 0, 0.0, []
        names = (dispatch.JAXPR_TO_MLIR_MODULE_EVENT,)

        def listen(event, duration, **kw):
            if self.on and event in names:
                self.n += 1
                self.secs += duration
                self.names.append(str(kw.get("fun_name")))

        jax.monitoring.register_event_duration_secs_listener(listen)


class DecodeLog:
    """Wraps the engine's decode-chunk program to record, per call, the
    live slots' key counts: what decode attention needs to read."""

    def __init__(self, engine):
        import numpy as np
        self.calls = []           # (traced, live lengths, D)
        self.traced = False
        inner = engine._decode_chunk_fn
        D = engine.ro.decode_chunk

        def wrapped(params, cache, last_token, cache_len, active, *rest):
            live = np.array([t is not None for t in engine.slots], bool)
            lens = engine.cache_len[live].astype(np.float64)
            self.calls.append((self.traced, lens, D))
            return inner(params, cache, last_token, cache_len, active, *rest)

        engine._decode_chunk_fn = wrapped


def build(cell_name, seed, *, root=ROOT, spans=False):
    """Configuration, traffic and trainer of a cell, weights from ``seed``."""
    import jax.numpy as jnp
    from bench import spec, weights
    from bench.traffic import TrafficTask
    from repro.common.config import RolloutConfig, TrainConfig
    from repro.core.copris import CoPRISTrainer

    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, cell_name)
    cfg = spec.load_config(bench, cell["config"], root)
    fam = spec.family(cfg, root / "bench")
    tfc = spec.load_traffic(cell["traffic"], root / "bench")
    kw = jnp.asarray(weights.np_key(seed))
    # the sampling stream is the traffic's, not the seed's: with the stop
    # token's logit a fixed height over a near-flat background, the same
    # stream puts the stops at (nearly) the same places under every seed's
    # weights and prompts, so every seed runs the same set of lengths
    kt = jnp.asarray(weights.np_key(tfc["sample_seed"]))
    task = TrafficTask(tfc, cfg["vocab_size"], seed, spans=spans)
    ro = RolloutConfig(
        batch_size=tfc["batch_size"], group_size=tfc["group_size"],
        max_prompt_len=tfc["prompt_len_max"],
        max_response_len=tfc["max_response_len"],
        temperature=tfc["temperature"], concurrency=tfc["concurrency"],
        mode=tfc["mode"], decode_chunk=tfc["decode_chunk"],
        kv_backend=tfc["kv_backend"])
    tc = TrainConfig(
        lr=tfc["lr"], warmup_steps=tfc["warmup_steps"],
        clip_low=tfc["clip_low"], clip_high=tfc["clip_high"],
        is_ratio_cap=tfc["is_ratio_cap"], weight_decay=tfc["weight_decay"],
        betas=tuple(tfc["betas"]), eps=tfc["adam_eps"],
        grad_clip=tfc["grad_clip"], seed=seed % (1 << 31),
        microbatches=cfg.get("train_microbatches", 1))
    params = fam.make_params(cfg, tfc, kw)
    tr = CoPRISTrainer(fam.model_config(cfg, cell["config"]), ro, tc, task,
                       eos_id=tfc["eos_id"], key=kt, params=params)
    del params
    return dict(bench=bench, cell=cell, cfg=cfg, family=fam, traffic=tfc,
                task=task, trainer=tr, key_weights=kw)


def _batch_of(tr):
    b = tr.last_batch
    keep = ("tokens", "loss_mask", "behaviour_logp", "stage_ids",
            "prompt_lens", "total_lens", "rewards")
    return {k: b[k].copy() for k in keep}


def _leaf_norms(tree, scale=1.0):
    import jax
    import jax.numpy as jnp
    return [float(x) for x in jax.device_get(
        [jnp.linalg.norm(l.astype(jnp.float32).ravel()) * scale
         for l in jax.tree.leaves(tree)])]


def checked_steps(b):
    """The first CHECK_STEPS steps through the trainer's own ``step()``,
    with what the reference compares: each step's loss and packed batch,
    the first gradient as Adam got it (its first moment over 1 - beta1),
    and the params' change over the steps."""
    import jax
    import jax.numpy as jnp
    tr = b["trainer"]
    outs, batches, g0 = [], [], None
    for k in range(CHECK_STEPS):
        outs.append(tr.step())
        batches.append(_batch_of(tr))
        if k == 0:
            g0 = _leaf_norms(tr.opt_state["m"],
                             1.0 / (1.0 - b["traffic"]["betas"][0]))
    p0 = b["family"].make_params(b["cfg"], b["traffic"], b["key_weights"])
    delta = _leaf_norms(jax.tree.map(jnp.subtract, tr.params, p0))
    del p0
    return dict(losses=[o["pg_loss"] for o in outs], batches=batches,
                g0_norms=g0, delta_norms=delta,
                grad_norm0=outs[0]["grad_norm"],
                names=leaf_names(tr.params))


def warm_shapes(b):
    """Run every program shape the window can meet that the first steps
    may not have: the batched prefill for fresh prompts at each power-of-two
    row count, the whole-pool prefill at each length bucket (the stage-start
    refill of resumed partials), and the train step at each length a batch
    is padded to (``train_lens``). Prefill rows are aimed at an
    out-of-range slot, so the cache is left as it was."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.rollout import PREFILL_BUCKET, prefill_pad_dims
    tr = b["trainer"]
    eng = tr.engine
    tf = b["traffic"]
    key = jnp.zeros((2,), jnp.uint32)
    shapes = set()
    for n in range(1, eng.pool + 1):
        shapes.add(prefill_pad_dims([tf["prompt_len_max"]], n, n))
    for L in range(tf["prompt_len_min"], eng.max_len, PREFILL_BUCKET):
        shapes.add(prefill_pad_dims([L], eng.pool, eng.pool))
    for S, nr, ns in sorted(shapes):
        i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
        tok, logp, eng.cache = eng._prefill_batch_fn(
            tr.params, eng.cache, i32(np.zeros((nr, S))),
            i32(np.full(nr, S)), i32(np.full(ns, eng.pool)),
            i32(np.zeros(ns)), i32(np.zeros((1, 1))), i32(np.zeros(ns)),
            i32(np.zeros(ns)), i32(np.zeros(ns)), key)
    N = tf["batch_size"] * tf["group_size"]
    p_stop = b["family"].design(b["cfg"], tf)["stop_prob_effective"]
    train_T = train_lens(tf, p_stop, eng.max_len, PREFILL_BUCKET)
    jax.block_until_ready((tok, logp, eng.cache))
    for T in train_T:
        fake = dict(tokens=np.zeros((N, T), np.int32),
                    loss_mask=np.zeros((N, T), np.float32),
                    behaviour_logp=np.zeros((N, T), np.float32),
                    rewards=np.zeros(N, np.float32))
        jb, lr = tr.train_inputs(fake, tr.stage)
        # one update's outputs (a second params and optimizer state) at a
        # time: the chip holds no two
        jax.block_until_ready(tr._train_step(tr.params, tr.opt_state, jb, lr))
    return len(shapes) + len(train_T)


RARE_BATCH = 1 / 400  # a train length only rarer batches are padded to


def train_lens(tf, stop_prob, max_len, bucket):
    """The lengths a train batch is padded to that the warm-up runs. A batch
    is padded to its longest trajectory, rounded up to ``bucket``; its N
    responses are geometric (per-token stop ``stop_prob``) up to the cap,
    so the longest is under r tokens with probability (1 - (1-p)^r)^N.
    Lengths only a batch rarer than ``RARE_BATCH`` is padded to (taking
    the shortest prompt) are left out."""
    N = tf["batch_size"] * tf["group_size"]
    r = math.log1p(-RARE_BATCH ** (1.0 / N)) / math.log1p(-stop_prob)
    lo = tf["prompt_len_min"] + min(tf["max_response_len"], int(r))
    lo = -(-lo // bucket) * bucket
    return range(max(bucket, min(lo, max_len)), max_len + 1, bucket)


def lengths_of(batches, cap, eos_id):
    import numpy as np
    resp, trunc = [], []
    for bt in batches:
        for n in range(bt["tokens"].shape[0]):
            p, t = bt["prompt_lens"][n], bt["total_lens"][n]
            resp.append(t - p)
            trunc.append(t - p >= cap and bt["tokens"][n, t - 1] != eos_id)
    resp = np.asarray(resp, np.float64)
    if resp.size == 0:
        return {}
    return dict(n=int(resp.size), mean=float(resp.mean()),
                p50=float(np.percentile(resp, 50)),
                p95=float(np.percentile(resp, 95)),
                truncated=float(np.mean(trunc)))


def compare(chk, ref, limits, beta_rule=1e-3):
    """The compared numbers, each ``(value, limit)``.

    ``loss_gap``: the widest gap of a step's loss, in units of the step's
    mean |advantage|. ``grad_gap``: the norm of the first gradient as Adam
    got it (the system's from its first moment after one step), by the
    worst leaf. ``update_gap``: the norm of the params' change over the
    checked steps, by the worst leaf the reference's first gradient moves
    (a leaf under ``beta_rule`` of the median leaf's gradient moves by
    round-off and weight decay alone). ``behaviour_gap``: the widest gap between a sampled token's
    behaviour log-prob and the reference's under the params that sampled
    it."""
    import numpy as np
    K = len(ref["losses"])
    loss_gap = max(abs(chk["losses"][k] - ref["losses"][k])
                   / max(ref["adv_scale"][k], 1e-12) for k in range(K))
    def worst(prog, refn, keep):
        # the gap of each leaf's norm against the reference's, over the
        # reference's norm of that leaf or of the median leaf if larger
        prog, refn = np.asarray(prog), np.asarray(refn)
        floor = max(float(np.median(refn)), 1e-30)
        gap = np.abs(prog - refn) / np.maximum(refn, floor)
        return float(gap[keep].max())

    g_ref = np.asarray(ref["g0_norms"])
    moved = g_ref >= beta_rule * np.median(g_ref)
    grad_gap = worst(chk["g0_norms"], g_ref, np.ones(g_ref.size, bool))
    update_gap = worst(chk["delta_norms"], ref["delta_norms"], moved)
    beh = 0.0
    for bt, rl in zip(chk["batches"], ref["ref_logp"]):
        sel = (bt["loss_mask"][:, 1:] > 0) & np.isfinite(rl)
        if sel.any():
            beh = max(beh, float(np.abs(bt["behaviour_logp"][:, 1:][sel]
                                        - rl[sel]).max()))
    vals = dict(loss_gap=loss_gap, grad_gap=grad_gap, update_gap=update_gap,
                behaviour_gap=beh)
    return {k: (v, limits.get(k)) for k, v in vals.items()}


def behaviour_worst(chk, ref, eos_id):
    """Where the widest behaviour log-prob gaps lie, for the log: the three
    widest tokens, each with its step, row, position, prompt and total
    length, response index, stage, whether a prefill produced it (the
    first token of a response or of a stage: a re-prefill's) and the
    tokens decoded since that prefill's, whether it is the stop token, and
    both log-probs; and how many tokens, produced by prefill or by decode,
    read over 0.015."""
    import numpy as np
    n, over = 3, 0.015
    n_over = {"prefill": 0, "decode": 0}
    top = []
    for k, (bt, rl) in enumerate(zip(chk["batches"], ref["ref_logp"])):
        sel = (bt["loss_mask"][:, 1:] > 0) & np.isfinite(rl)
        gap = np.where(sel, np.abs(bt["behaviour_logp"][:, 1:] - rl), -1.0)
        st = bt["stage_ids"]
        t = np.arange(1, st.shape[1])
        resp = t[None, :] - bt["prompt_lens"][:, None]
        pre = (resp == 0) | (st[:, 1:] != st[:, :-1])
        n_over["prefill"] += int((sel & pre & (gap > over)).sum())
        n_over["decode"] += int((sel & ~pre & (gap > over)).sum())
        for i in np.argsort(-gap, axis=None)[:n]:
            r, j = np.unravel_index(i, gap.shape)
            if gap[r, j] >= 0:
                top.append((float(gap[r, j]), k, int(r), int(j) + 1,
                            int(resp[r, j]), bool(pre[r, j])))
    out = []
    for g, k, r, t, rs, p in sorted(top, reverse=True)[:n]:
        bt = chk["batches"][k]
        st, u = bt["stage_ids"][r], t
        while u - 1 >= bt["prompt_lens"][r] and st[u - 1] == st[t]:
            u -= 1
        out.append(dict(gap=g, step=k, row=r, pos=t, since_prefill=t - u,
                        prompt=int(bt["prompt_lens"][r]),
                        total=int(bt["total_lens"][r]), resp=rs,
                        stage=int(bt["stage_ids"][r, t]), prefill=p,
                        stop=bool(bt["tokens"][r, t] == eos_id),
                        system=float(bt["behaviour_logp"][r, t]),
                        reference=float(ref["ref_logp"][k][r, t - 1])))
    return dict(worst=out, over=over, n_over=n_over)


def judge(checks, limits):
    """Whether every compared number is within its limit, and the numbers
    compared. A number that the cell's limits file lists with a null limit
    (no reading of the control or of a fault stands far enough above the
    system's to set one from) is not compared; one the file leaves out
    fails."""
    compared = {k: c for k, c in checks.items()
                if k not in limits or limits[k] is not None}
    ok = all(lim is not None and v == v and v <= lim
             for v, lim in compared.values())
    return ok, compared


def leaf_report(chk, ref, names, n=3):
    """The leaves that set the gradient and update gaps, for the log."""
    import numpy as np
    out = {"grad_norm0": (chk["grad_norm0"], ref["grad_norm0"])}
    for key in ("g0_norms", "delta_norms"):
        p, r = np.asarray(chk[key]), np.asarray(ref[key])
        gap = np.abs(p - r) / np.maximum(r, np.median(r))
        out[key] = [(names[i], float(p[i]), float(r[i]), float(gap[i]))
                    for i in np.argsort(-gap)[:n]]
    return out


def leaf_names(params):
    import jax
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def reference_run(b, chk, precision="highest"):
    """The family's reference over the set-up steps' batches, from the
    seed."""
    tf, fam = b["traffic"], b["family"]
    make = lambda: fam.make_params(b["cfg"], tf, b["key_weights"])
    hyper = dict(tf, betas=tuple(tf["betas"]),
                 microbatches=b["cfg"].get("train_microbatches", 1))
    out = fam.reference_run(b["cfg"], hyper, make(), chk["batches"],
                            b["task"].reward_of, precision, make)
    out.pop("params")
    return out


def window(b, seconds, traced, trace_dir, counter, dlog):
    """Steps until ``seconds`` have passed; returns what the metrics need."""
    import jax
    tr, task = b["trainer"], b["task"]
    e0 = tr.engine.stats_snapshot()
    p0, n0 = task.prompt_tokens, task.prompts
    steps, batches = [], []
    traced_steps, trace_stop_s = 0, 0.0
    counter.on = True
    t0 = time.perf_counter()
    tracing = traced
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans and dispatches, no frames
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        if dlog:
            dlog.traced = True
    while True:
        if traced:
            with jax.profiler.TraceAnnotation("bench.step"):
                out = tr.step()
        else:
            out = tr.step()
        steps.append(out)
        batches.append(_batch_of(tr))
        if tracing:
            traced_steps += 1
            if len(steps) == TRACE_STEPS:
                span.__exit__(None, None, None)
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                trace_stop_s = time.perf_counter() - t_stop
                tracing = False
                if dlog:
                    dlog.traced = False
        if time.perf_counter() - t0 >= seconds and not tracing:
            break
    wall = time.perf_counter() - t0
    counter.on = False
    e1 = tr.engine.stats_snapshot()
    eng = {k: e1.get(k, 0) - e0.get(k, 0) for k in e1}
    return dict(steps=steps, batches=batches, window_s=wall, engine=eng,
                prompt_tokens=task.prompt_tokens - p0,
                prompts=task.prompts - n0,
                traced_steps=traced_steps, trace_stop_s=trace_stop_s)


def end_to_end(w, setup_s):
    """The end-to-end metrics by name. A metric ``<name>.<suffix>`` in
    ``BENCHMARK.json`` reports ``<name>`` under a bound of its own, in the
    cells its ``workloads`` lists."""
    import numpy as np
    toks = sum(float(np.sum(bt["loss_mask"])) for bt in w["batches"])
    return {"trained_tokens_per_s": toks / w["window_s"],
            "step_s": w["window_s"] / len(w["steps"]),
            "setup_s": setup_s}


def main(argv=None, *, require_tpu=True, root=ROOT, hooks=None) -> int:
    """One run; ``require_tpu=False`` and ``hooks`` (called with the built
    cell before its first step) are for the tests on the CPU."""
    t_start = time.perf_counter()
    args = parse(argv)
    from bench import spec
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()

    b = build(args.workload, args.seed, root=root, spans=bool(args.trace))
    if hooks:
        hooks(b)
    tr = b["trainer"]
    t = time.perf_counter()
    chk = checked_steps(b)
    log(f"bench: checked steps {[round(x, 6) for x in chk['losses']]} "
        f"(pg_loss) in {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    n_warm = warm_shapes(b)
    log(f"bench: warmed {n_warm} program shapes in "
        f"{time.perf_counter() - t:.3f}s")
    dlog = DecodeLog(tr.engine) if args.trace else None
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    w = window(b, args.seconds, bool(args.trace), trace_dir, counter, dlog)
    tf = b["traffic"]
    L = lengths_of(w["batches"], tf["max_response_len"], tf["eos_id"])
    from bench import weights
    tgt = weights.expected_lengths(
        b["family"].design(b["cfg"], tf)["stop_prob_effective"],
        tf["max_response_len"])
    log(f"bench: window {w['window_s']:.3f}s (trace writing "
        f"{w['trace_stop_s']:.3f}s), {len(w['steps'])} steps, "
        f"compiles inside the window {counter.n} ({counter.secs:.3f}s) "
        f"{counter.names}")
    log("bench: response lengths trained in the window: " + json.dumps(L)
        + " target " + json.dumps(tgt))
    log("bench: steps " + json.dumps([
        {k: round(s[k], 4) for k in STEP_LOG if k in s} for s in w["steps"]]))
    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind,
                         "count": cell["chips"], "memory_peak_bytes": peak}}
    traj = len(w["steps"]) * tf["batch_size"] * tf["group_size"]
    result["attempted"] = traj
    if args.trace:
        from bench import roofline, trace as tr_mod
        red = tr_mod.reduce(tr_mod.find_xplane(trace_dir))
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
        ctx = dict(cfg=b["cfg"], family=b["family"], traffic=tf, w=w,
                   trace=red,
                   peaks=roofline.peaks(devs[0].device_kind)
                   if require_tpu else roofline.PEAKS["TPU v5 lite"],
                   chips=cell["chips"], decode_calls=dlog.calls)
        for m in spec.metrics_for(bench, args.workload, "per_layer"):
            v = spec.load_reader(m["name"], root / "bench")(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        log("bench: device ops " + json.dumps(
            sorted(red.op_s.items(), key=lambda kv: -kv[1])[:40]))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = end_to_end(w, setup_s)
        for m in spec.metrics_for(bench, args.workload, "end_to_end"):
            v = e2e[m["name"].split(".")[0]]
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    # free the trainer before the reference runs on the same chips
    tr.close()
    b.pop("trainer")
    del tr, w
    gc.collect()
    t = time.perf_counter()
    ref = reference_run(b, chk)
    limits = spec.load_limits(args.workload, root / "bench")
    checks = compare(chk, ref, limits)
    log("bench: worst leaves " + json.dumps(leaf_report(chk, ref,
                                                        chk["names"])))
    log("bench: behaviour worst " + json.dumps(
        behaviour_worst(chk, ref, tf["eos_id"])))
    ok, compared = judge(checks, limits)
    log("bench: not compared " + json.dumps(
        {k: v for k, (v, _) in checks.items() if k not in compared}))
    log(f"bench: reference in {time.perf_counter() - t:.3f}s")
    result["correct"] = bool(ok)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
