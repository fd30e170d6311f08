"""RL training launcher: the CoPRIS trainer on the visible devices (the
Pallas kernel path on one TPU device, the XLA path elsewhere).

    PYTHONPATH=src python -m repro.launch.train \
        --arch tiny --mode copris --steps 200 --concurrency 16 \
        --sft-warmup 150 --out runs/tiny_copris

Writes metrics.jsonl per step and checkpoints every --ckpt-every steps.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import RolloutConfig, TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.core.copris import CoPRISTrainer
from repro.data.sft import sft_warmup
from repro.data.tasks import (AdditionTask, EOS, MultiTurnMathTask,
                              ToolCallTask)
from repro.launch.mesh import make_disaggregated_meshes
from repro.models import model as M


def make_task(name: str, seed: int):
    """--task registry. Multi-turn tasks expose make_env(spec) and route
    rollouts through the async environment worker."""
    if name == "addition":
        return AdditionTask(max_value=20, seed=seed)
    if name == "multiturn_math":
        return MultiTurnMathTask(max_value=9, num_turns=2, seed=seed)
    if name == "toolcall":
        return ToolCallTask(max_value=9, seed=seed)
    raise ValueError(f"unknown task {name!r}")


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced variant of --arch")
    ap.add_argument("--mode", default="copris",
                    choices=["copris", "sync", "naive_partial"])
    ap.add_argument("--task", default="addition",
                    choices=["addition", "multiturn_math", "toolcall"],
                    help="multiturn_math / toolcall run multi-turn episodes "
                         "through the async environment worker (env tokens "
                         "are loss-masked; slots are yielded during env "
                         "waits)")
    ap.add_argument("--env-timeout", type=float, default=0.0,
                    help="per-env-step deadline in seconds (0 = none); a "
                         "step past it ends the episode with the reward so "
                         "far instead of wedging the stage")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-response", type=int, default=24)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--no-is", action="store_true",
                    help="disable cross-stage IS correction (ablation)")
    ap.add_argument("--overlap", action="store_true",
                    help="one-step-async pipeline: rollout for stage k+1 "
                         "runs on a background thread while stage k trains")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="max optimizer updates the train step may be ahead "
                         "of the params that generated its batch (K > 1 = "
                         "multi-step async pipeline)")
    ap.add_argument("--disaggregated", action="store_true",
                    help="route every published params version through the "
                         "versioned ParamStore reshard (train FSDP layout "
                         "-> rollout serve_tp_only layout); with several "
                         "devices visible, half train and half roll out; "
                         "requires --overlap")
    ap.add_argument("--adaptive-concurrency", action="store_true",
                    help="overlap-aware N' controller: adjust the in-flight "
                         "rollout target between stages from observed "
                         "rollout-vs-train timing")
    ap.add_argument("--concurrency-min", type=int, default=0,
                    help="adaptive N' lower bound (0 = concurrency // 4)")
    ap.add_argument("--concurrency-max", type=int, default=0,
                    help="adaptive N' upper bound (0 = concurrency; the "
                         "slot pool is sized to this)")
    ap.add_argument("--sft-warmup", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="runs/default")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--eval-every", type=int, default=25)
    return ap


def build_trainer(args, cfg, task, *, params=None) -> CoPRISTrainer:
    """The trainer ``main`` runs, from its parsed arguments: rollout and
    train configs, the disaggregated meshes over the visible devices, and
    the platform's kernel path (``CoPRISTrainer``'s default)."""
    ro = RolloutConfig(batch_size=args.batch_size, group_size=args.group_size,
                       max_prompt_len=16, max_response_len=args.max_response,
                       concurrency=args.concurrency, mode=args.mode,
                       adaptive_concurrency=args.adaptive_concurrency,
                       concurrency_min=args.concurrency_min,
                       concurrency_max=args.concurrency_max,
                       env_step_timeout=args.env_timeout)
    tc = TrainConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps,
                     use_is_correction=not args.no_is, seed=args.seed,
                     overlap=args.overlap, max_staleness=args.max_staleness,
                     disaggregated=args.disaggregated)
    train_mesh = rollout_mesh = None
    if args.disaggregated and jax.device_count() > 1:
        train_mesh, rollout_mesh = make_disaggregated_meshes()
    return CoPRISTrainer(cfg, ro, tc, task, eos_id=EOS, params=params,
                         train_mesh=train_mesh, rollout_mesh=rollout_mesh)


def main(argv=None):
    args = make_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    task = make_task(args.task, args.seed)
    os.makedirs(args.out, exist_ok=True)

    params = None
    if args.resume:
        state = ckpt.load(args.resume)
        params = state["params"]
        print(f"resumed from {args.resume}")
    elif args.sft_warmup > 0:
        params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
        print(f"SFT warmup {args.sft_warmup} steps…")
        # multi-turn tasks have no supervised demos; warm up on the
        # single-turn surrogate (digits + EOS — the per-turn answer format
        # every env here shares)
        demo_task = (task if hasattr(task, "demo")
                     else AdditionTask(max_value=20, seed=args.seed))
        params, loss = sft_warmup(params, cfg, demo_task,
                                  steps=args.sft_warmup, log_every=50)
        print(f"  warmup done (loss {loss:.3f})")

    tr = build_trainer(args, cfg, task, params=params)
    if args.resume:
        # restore republishes through the ParamStore so the rollout side
        # acquires the checkpointed weights, not the construction version
        tr.restore(opt_state=state["opt_state"], stage=state["stage"])

    mpath = os.path.join(args.out, "metrics.jsonl")
    try:
        with open(mpath, "a") as mf:
            for i in range(args.steps):
                out = tr.step()
                mf.write(json.dumps(out) + "\n")
                mf.flush()
                if i % 5 == 0:
                    stale = (f" stale={out['param_staleness']}"
                             f" saved={out['overlap_saved_time']:.1f}s"
                             if args.overlap else "")
                    if args.adaptive_concurrency:
                        stale += f" N'={out['concurrency_target']}"
                    if out.get("env_steps"):
                        stale += (f" env={out['env_steps']}s/"
                                  f"{out['env_turns']}t")
                    print(f"step {out['step']:4d} reward={out['reward_mean']:.3f} "
                          f"loss={out['pg_loss']:+.4f} ratio={out['ratio_mean']:.3f} "
                          f"off={out['off_policy_frac']:.2f} "
                          f"t={out['step_time']:.1f}s{stale}")
                if args.eval_every and (i + 1) % args.eval_every == 0:
                    from repro.eval.passk import evaluate as eval_passk
                    acc = tr.evaluate(n_prompts=16)
                    # safe_task serialises prompt sampling against the
                    # overlapped trainer's background rollout thread
                    pk = eval_passk(tr.params, cfg, tr.safe_task, eos_id=EOS,
                                    n_prompts=8, samples_per_prompt=8,
                                    max_response=args.max_response, ks=(1, 8))
                    print(f"  eval@{out['step']}: greedy {acc:.3f} "
                          f"pass@1 {pk['pass@1']:.3f} pass@8 {pk['pass@8']:.3f}")
                if (i + 1) % args.ckpt_every == 0:
                    p = os.path.join(args.out, f"ckpt_{tr.stage}.zpkl")
                    ckpt.save(p, {"params": tr.params, "opt_state": tr.opt_state,
                                  "stage": tr.stage})
                    print(f"  saved {p}")
        print("final eval:", tr.evaluate(n_prompts=32))
    finally:
        tr.close()


if __name__ == "__main__":
    main()
