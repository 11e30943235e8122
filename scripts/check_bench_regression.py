"""Perf regression gate over BENCH_mc.json (CI `perf` job).

Compares a freshly measured BENCH_mc.json against the committed baseline
(`benchmarks/BENCH_mc.baseline.json` — the generated root BENCH_mc.json
itself stays gitignored) and fails on a >20% slowdown of the gated
metric. CI runners differ wildly in absolute speed, so the gated metric
is a *relative* one each run measures on its own box:
`planner_grid.speedup`, batched `plan_launch` vs. the in-run pinned
scalar loop. Absolute `batched_s` and the ensemble's traj/s are reported
for context but never gated.

    python scripts/check_bench_regression.py [--max-slowdown 0.2] \
        [--baseline benchmarks/BENCH_mc.baseline.json] \
        [--current BENCH_mc.json]

Exit nonzero when the current speedup < (1 - max_slowdown) * baseline.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path: str) -> dict:
    return json.loads(pathlib.Path(path).read_text(encoding="utf-8"))


def check(baseline: dict, current: dict, max_slowdown: float) -> list:
    errors = []
    base_grid = baseline.get("planner_grid", {})
    cur_grid = current.get("planner_grid", {})
    base_speedup = base_grid.get("speedup")
    cur_speedup = cur_grid.get("speedup")
    if base_speedup is None or cur_speedup is None:
        return ["planner_grid.speedup missing from baseline or current"]
    floor = (1.0 - max_slowdown) * base_speedup
    print(f"planner_grid: baseline speedup {base_speedup}x "
          f"(batched {base_grid.get('batched_s')}s), current "
          f"{cur_speedup}x (batched {cur_grid.get('batched_s')}s); "
          f"floor {floor:.1f}x")
    if cur_speedup < floor:
        errors.append(
            f"planner-grid regression: speedup {cur_speedup}x fell below "
            f"{floor:.1f}x (= {1 - max_slowdown:.0%} of the committed "
            f"{base_speedup}x baseline)")
    ens_b = baseline.get("ensemble", {}).get("traj_per_s")
    ens_c = current.get("ensemble", {}).get("traj_per_s")
    if ens_b and ens_c:  # informational only: absolute, machine-dependent
        print(f"ensemble: baseline {ens_b} traj/s, current {ens_c} traj/s")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline",
                    default=str(ROOT / "benchmarks"
                                / "BENCH_mc.baseline.json"),
                    help="committed BENCH_mc.json snapshot")
    ap.add_argument("--current", default=str(ROOT / "BENCH_mc.json"),
                    help="freshly measured BENCH_mc.json")
    ap.add_argument("--max-slowdown", type=float, default=0.2,
                    help="allowed fractional speedup loss (default 0.2)")
    args = ap.parse_args(argv)
    errors = check(_load(args.baseline), _load(args.current),
                   args.max_slowdown)
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    if not errors:
        print("perf gate OK")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
