#!/usr/bin/env python3
"""Readings that the correctness limits are set from, many seeds in one
process (not part of a benchmark run).

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--control 1]

For each seed: the cell's trainer runs the set-up steps that a benchmark
run checks, is freed, and the float32 reference follows them; the compared
numbers are printed (the lower readings). With ``--control 1`` the
reference is also computed in float8 in the system's place (the control)
and compared with the float32 one (the upper readings). One JSON line per
seed and side.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):          # run as a script: bench/ is on the path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.run import (ROOT, behaviour_worst, build,  # noqa: E402
                       checked_steps, compare, leaf_report, reference_run)


def control_checks(b, chk, ref):
    """The control's numbers: the float8 reference in the system's place,
    on the same batches, against the float32 reference."""
    ctl = reference_run(b, chk, precision="fp8")
    return compare(_as_system(chk, ctl), ref, {})


def _as_system(chk, out):
    """A reference run's readings in the place of the system's."""
    return dict(chk, losses=out["losses"], g0_norms=out["g0_norms"],
                delta_norms=out["delta_norms"],
                batches=[dict(bt, behaviour_logp=_beh_of(bt, lp))
                         for bt, lp in zip(chk["batches"], out["ref_logp"])])


def fault_checks(b, chk, ref):
    """Faults planted in the float32 reference put in the system's place,
    each compared with the sound reference: half of each batch left out
    (the mean over the rest), every reward inverted where it is produced,
    and the first response token of every row altered where it is
    produced (its behaviour log-prob stays the sampled token's). A state
    left unchanged reads 1 on ``update_gap`` and needs no run."""
    import numpy as np
    out = {}
    half = [dict(bt, loss_mask=bt["loss_mask"].copy()) for bt in chk["batches"]]
    for bt in half:
        bt["loss_mask"][bt["loss_mask"].shape[0] // 2:] = 0.0
    got = reference_run(b, dict(chk, batches=half))
    out["half_batch"] = compare(_as_system(chk, got), ref, {})
    task = b["task"]
    inverted = dict(b, task=_Inverted(task))
    got = reference_run(inverted, chk)
    out["answer_altered"] = compare(_as_system(chk, got), ref, {})
    V = b["cfg"]["vocab_size"]
    alt = []
    for bt in chk["batches"]:
        bt = dict(bt, tokens=bt["tokens"].copy())
        for n, p in enumerate(bt["prompt_lens"]):
            t = bt["tokens"][n, p]
            if t != b["traffic"]["eos_id"]:
                bt["tokens"][n, p] = 2 + (t + 1) % (V - 2)
        alt.append(bt)
    got = reference_run(b, dict(chk, batches=alt))
    # the system would record the sampled token's log-prob (the sound
    # reference's) beside the altered token
    sys_beh = _as_system(chk, ref)["batches"]
    out["token_altered"] = compare(
        dict(chk, batches=[dict(a, behaviour_logp=s["behaviour_logp"])
                           for a, s in zip(alt, sys_beh)]),
        dict(ref, ref_logp=got["ref_logp"]), {})
    return {k: {n: v for n, (v, _) in c.items()} for k, c in out.items()}


class _Inverted:
    def __init__(self, task):
        self.task = task

    def reward_of(self, toks):
        return 1.0 - self.task.reward_of(toks)


def _beh_of(bt, ref_lp):
    import numpy as np
    beh = bt["behaviour_logp"].copy()
    sel = np.isfinite(ref_lp)
    beh[:, 1:][sel] = ref_lp[sel]
    return beh


def one_seed(cell, seed, *, control, faults=False, root=ROOT):
    b = build(cell, seed, root=root)
    chk = checked_steps(b)
    b["trainer"].close()
    b.pop("trainer")
    gc.collect()
    t = time.perf_counter()
    ref = reference_run(b, chk)
    secs = time.perf_counter() - t
    rows = [dict(seed=seed, side="system", secs=secs,
                 **{k: v for k, (v, _) in compare(chk, ref, {}).items()},
                 leaves=leaf_report(chk, ref, chk["names"]),
                 behaviour=behaviour_worst(chk, ref,
                                           b["traffic"]["eos_id"]))]
    if control:
        t = time.perf_counter()
        got = control_checks(b, chk, ref)
        rows.append(dict(seed=seed, side="control",
                         secs=time.perf_counter() - t,
                         **{k: v for k, (v, _) in got.items()}))
    if faults:
        for name, vals in fault_checks(b, chk, ref).items():
            rows.append(dict(seed=seed, side="fault:" + name, **vals))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0,
                    help="plant the faults in the reference on the first N seeds")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for i, seed in enumerate(args.seeds):
        for row in one_seed(args.workload, seed, control=bool(args.control),
                            faults=i < args.faults):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
