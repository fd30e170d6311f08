"""Per-PR benchmark artifact.

Runs the cheap, CI-safe subset of the benchmark harness — the kernel
microbenchmarks (paged-vs-dense decode attention, fused-vs-unfused IS+GRPO
loss, the XLA sampler oracle), the analytic decode-attention and RL-math
rooflines, and the real-engine equal-HBM concurrency row — and writes one
JSON blob, so kernel regressions show up as an artifact diff rather than a
silent drift. ``BENCH_rl_math_kernels.json`` at the repo root is the
committed per-PR snapshot; CI re-runs the harness and diffs against it
(``--diff-against``): correctness-check PASS→FAIL and analytic-row drift
fail the job, timing ratios are reported only.

    PYTHONPATH=src python -m benchmarks.bench_artifact \
        --out BENCH_rl_math_kernels.json \
        --diff-against BENCH_rl_math_kernels.json

With ``--sim-json sim_smoke.json`` the rollout-simulator smoke rows (written
by ``benchmarks/sim.py --json``) are folded into the blob, and the artifact
also times the static analyzer itself (full AST scan + the PAL205 interval
analysis) in subprocesses so analyzer-runtime regressions show up in the
same diff.

Exits nonzero if a kernel interpret-mode correctness check FAILs (timing
ratios are recorded but never gate CI — container CPUs are too noisy)."""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

import jax


def _timed_analysis(args_list) -> dict:
    """Run ``python -m repro.analysis <args>`` in a subprocess, return
    wall seconds + exit code. Runtime is recorded, never gated — the
    analysis/ir-lint CI jobs own the gating."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    # host-side analysis: this process may already hold the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args_list],
        capture_output=True, text=True, env=env)
    return {"args": args_list,
            "wall_s": round(time.perf_counter() - t0, 3),
            "returncode": proc.returncode}


def analyzer_runtime_rows() -> dict:
    return {
        "ast_full_scan": _timed_analysis(["--format=json"]),
        "irlint_pal205": _timed_analysis(
            ["--ir", "--select", "PAL205", "--no-baseline",
             "--format=json"]),
    }


def collect() -> dict:
    from benchmarks import kernelbench, rooflines, table2_concurrency

    rows = []
    kernelbench.main(rows)
    rows.extend(rooflines.kernel_rows())
    rows.extend(rooflines.rl_math_rows())
    rows.append(table2_concurrency.kv_equal_hbm_row())

    by_name = {n: (v, d) for n, v, d in rows}
    dense = by_name["kernel_decode_attn_ref_4k"][0]
    paged = by_name["kernel_paged_decode_attn_ref_4k"][0]
    return {
        "backend": jax.default_backend(),
        "machine": platform.machine(),
        "rows": [{"name": n, "us_per_call": v, "derived": d}
                 for n, v, d in rows],
        "paged_vs_dense": {
            "decode_attn_ref_ratio": paged / dense,
            "kv_equal_hbm_live_slot_ratio":
                by_name["table2_kv_equal_hbm_256tok"][0],
            "hbm_bytes_saving_16k":
                by_name["roofline_decode_attn_paged_saving"][0],
        },
        # PR 10: fused RL-loop math — the analytic rows gate the
        # acceptance (<= 0.40 logits-bytes fraction, sampler saving > 1);
        # wall-clock ratios are recorded for the trajectory, never gated
        "rl_math": {
            "is_grpo_value_and_grad_time_ratio":
                by_name["kernel_fused_is_grpo_blocked_32k"][0]
                / by_name["kernel_is_grpo_unfused_ref_32k"][0],
            "is_grpo_fused_hbm_frac":
                by_name["roofline_is_grpo_fused_frac"][0],
            "sample_hbm_saving_plain":
                by_name["roofline_sample_saving_plain"][0],
            "sample_hbm_saving_topk_topp":
                by_name["roofline_sample_saving_topk_topp"][0],
        },
        "checks": {
            n: "interpret_allclose=PASS" in d
            for n, (_, d) in by_name.items() if "pallas_check" in n
        },
    }


def diff_against(blob: dict, path: str) -> list:
    """Diff this run against the last committed artifact: a correctness
    check that was PASS and is now FAIL (or vanished) is a regression; the
    analytic (roofline_*) values must be byte-stable; timing rows are
    reported as ratios but never gate (container CPUs are too noisy)."""
    with open(path) as f:
        old = json.load(f)
    regressions = []
    for name, was_ok in old.get("checks", {}).items():
        now = blob["checks"].get(name)
        if was_ok and not now:
            regressions.append(
                f"{name}: {'FAIL' if now is not None else 'row removed'} "
                f"(was PASS in {path})")
    old_rows = {r["name"]: r for r in old.get("rows", [])}
    for r in blob["rows"]:
        o = old_rows.get(r["name"])
        if o is None:
            print(f"  new row: {r['name']}")
            continue
        if r["name"].startswith("roofline_") and o["us_per_call"]:
            drift = abs(r["us_per_call"] - o["us_per_call"]) \
                / abs(o["us_per_call"])
            if drift > 1e-6:
                regressions.append(
                    f"{r['name']}: analytic value drifted "
                    f"{o['us_per_call']:.4g} -> {r['us_per_call']:.4g} — "
                    "model-constant changes must be justified in review")
        elif r["name"].startswith("kernel_") and o["us_per_call"]:
            ratio = r["us_per_call"] / o["us_per_call"]
            if ratio > 1.5 or ratio < 0.67:
                print(f"  timing drift (not gated): {r['name']} "
                      f"{ratio:.2f}x vs {path}")
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_rl_math_kernels.json")
    ap.add_argument("--sim-json", default=None, metavar="PATH",
                    help="fold the sim.py --json smoke rows into the blob "
                         "and record analyzer runtimes")
    ap.add_argument("--diff-against", default=None, metavar="PATH",
                    help="last committed artifact: fail on correctness-"
                         "check regressions and analytic-row drift, report "
                         "timing ratios")
    args = ap.parse_args(argv)
    blob = collect()
    if args.sim_json:
        with open(args.sim_json) as f:
            blob["sim_smoke"] = json.load(f).get("rows", [])
        blob["analyzer_runtime"] = analyzer_runtime_rows()
    with open(args.out, "w") as f:
        json.dump(blob, f, indent=1)
    print(f"wrote {args.out}")
    for sect in ("paged_vs_dense", "rl_math"):
        for k, v in blob[sect].items():
            print(f"  {k}: {v:.2f}")
    for k, v in blob.get("analyzer_runtime", {}).items():
        print(f"  {k}: {v['wall_s']}s (rc {v['returncode']})")
    rc = 0
    bad = [n for n, ok in blob["checks"].items() if not ok]
    if bad:
        print(f"FAILED correctness checks: {bad}", file=sys.stderr)
        rc = 1
    if args.diff_against and os.path.exists(args.diff_against):
        regressions = diff_against(blob, args.diff_against)
        for r in regressions:
            print(f"REGRESSION vs committed artifact: {r}", file=sys.stderr)
        rc = rc or (1 if regressions else 0)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
