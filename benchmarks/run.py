"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fig4-steps N]

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  table1  — end-to-end sync-vs-CoPRIS speedup (sim + real tiny model)
  table2  — concurrency ablation (N' sweep + naive partial)
  fig3    — context-length and model-size scaling
  fig4    — cross-stage IS ablation (real tiny RL runs)
  kernels — kernel reference timings + interpret-mode checks
  roofline— per (arch × shape) roofline terms from the dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys

from repro.common.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table1|table2|fig3|fig4|kernels|roofline")
    ap.add_argument("--fig4-steps", type=int, default=6)
    args = ap.parse_args()
    enable_compile_cache()

    rows = []
    sections = {}

    from benchmarks import (fig3_scaling, fig4_is_ablation, kernelbench,
                            rooflines, table1_end2end, table2_concurrency)
    sections["table1"] = table1_end2end.main
    sections["table2"] = table2_concurrency.main
    sections["fig3"] = fig3_scaling.main
    sections["fig4"] = lambda r: fig4_is_ablation.main(r, steps=args.fig4_steps)
    sections["kernels"] = kernelbench.main
    sections["roofline"] = rooflines.main

    todo = [args.only] if args.only else list(sections)
    print("name,us_per_call,derived")
    for name in todo:
        try:
            sections[name](rows)
        except Exception as e:  # keep the harness robust; report the failure
            rows.append((f"{name}_ERROR", -1.0, repr(e)[:120]))
        while rows:
            n, t, d = rows.pop(0)
            print(f"{n},{t:.2f},{d}")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
