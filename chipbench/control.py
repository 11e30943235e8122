"""Readings that the limits of `correct` are set from, at a cell's size.

    python3 chipbench/control.py --workload train-steady \\
        --seeds 11,12,13 --control-seeds 21,22,23

For each seed of `--seeds`, the program's readings against the reference
(the lower readings). For each seed of `--control-seeds`, the control:
the reference itself, put in the program's place and computed one
precision below what the configuration states (for a training cell,
matmul operands in float8 e4m3 under a bfloat16 configuration; for a
fleet cell, the loop's state in float32 under a float64 one). A training
cell also reads the faults it can have, planted in the reference put in
the program's place (half of each batch left out, the mean taken over the
rest); a state left unchanged reads 1 on `change_gap` by its definition
and needs no run.

Each driver module has the `readings` of its cells. The benchmark's own
runs never run this. One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    parse = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    from chipbench import harness
    driver = harness.Cell(ROOT, args.workload, 0, 0.0, False).driver()
    driver.readings(ROOT, args.workload, parse(args.seeds),
                    parse(args.control_seeds),
                    emit=lambda line: print(json.dumps(line), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
