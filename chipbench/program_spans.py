"""Run one cell traced, and read the program's own spans as well.

    python3 chipbench/program_spans.py --workload fleet-chaos --seed 7 \\
        --seconds 45

The run is that of `run.py --trace 1`: the same harness, driver, window,
comparison and result line. Three things are added:

* Python's garbage collections go into the trace as `python.gc` spans
  (`repro.core.profiler.trace_gc`);
* the trace is reduced by `sweep.reduce_file` with the driver's program
  spans (`PROGRAM_SPANS`), so the idle gaps in `breakdown` carry the
  program's span names, and every field the harness reads is unchanged;
* the metrics of `program_metrics.json` that list the cell are read by
  their `metrics/<name>.py` and added to `metrics`, and the line gains
  `program_spans` (per name: count, host seconds, device idle seconds
  held and stat sums) and `span_host_s` (host seconds per benchmark
  span).

Like `run.py`, it exits 1 and prints no result when JAX finds no TPU.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the program's spans each driver's calls run through
PROGRAM_SPANS = {
    "train": ("train.step", "train.data", "train.dispatch", "train.sync",
              "train.observe", "ckpt.copy", "ckpt.write", "ckpt.crc32",
              "ckpt.commit", "python.gc"),
    "fleet": ("fleet.run_many", "fleet.draws", "fleet.setup",
              "fleet.pools", "fleet.loop", "fleet.compact", "fleet.results",
              "python.gc"),
}


def run(root: str, workload: str, seed: int, seconds: float,
        t_start: float, require_tpu: bool = True,
        log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """One traced run of a cell, with the program's spans read."""
    from chipbench import harness, sweep, trace
    from repro.core.profiler import trace_gc

    cell = harness.Cell(root, workload, seed, seconds, True)
    names = PROGRAM_SPANS[cell.traffic["driver"]]
    reduced = []

    def reduce_file(path, span_names):
        t = time.monotonic()
        reduced.append(sweep.reduce_file(path, span_names, names))
        log(f"trace read and reduced in {time.monotonic() - t:.3f} s")
        return reduced[-1]

    trace_gc()
    harness_reduce, trace.reduce_file = trace.reduce_file, reduce_file
    try:
        result = harness.run_cell(root, workload, seed, seconds, True,
                                  t_start, require_tpu, log)
    finally:
        trace.reduce_file = harness_reduce
    red = reduced[-1]
    for m in harness.load_json(os.path.join(cell.dir,
                                            "program_metrics.json")):
        if workload in m["workloads"]:
            reader = harness.load_module(os.path.join(
                cell.dir, "metrics", m["name"] + ".py"))
            value = reader.read(red, {}, result["device"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    result["program_spans"] = {
        n: {"count": red.prog_count.get(n, 0),
            "host_s": red.prog_host_s.get(n, 0.0),
            "idle_s": red.prog_idle_s.get(n, 0.0),
            **red.prog_stats.get(n, {})}
        for n in sorted(set(red.prog_count) | set(red.prog_idle_s))}
    result["span_host_s"] = red.span_host_s
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run one cell traced, with the program's spans")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness, run as run_py
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE",
                          str(run_py.PREMAPPED_BYTES))
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds, T_START)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}; nothing is run elsewhere", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
