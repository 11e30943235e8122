"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload train-steady --seed 7 \\
        --seconds 45 --trace 0

Exits 1 and prints no result when JAX finds no TPU, or fewer chips than
the cell asks for. The last line of standard output is the result object;
the last lines of standard error are the numbers compared, each with its
limit.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREMAPPED_BYTES = 256 << 20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no src/repro under {ROOT}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 1
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime's own logs would otherwise go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The TPU runtime maps and pins a 4 GiB host staging buffer at start-up
    # by default. Without transparent hugepages that takes 4-7 s on a v5e
    # host and wanders with the host's memory, so it swamped `setup_s`.
    # The cells move far less through it: a train step's batch is 16 KiB,
    # and the fleet cell holds under 8 MB on the device in all.
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(PREMAPPED_BYTES))
    from chipbench import harness
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}; nothing is run elsewhere", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
