"""The chip benchmark: one cell of `BENCHMARK.json` per process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
`configs/<config>.json` (with the plain reference module it names beside
it), its traffic in `traffic/<traffic>.json` (which names the driver in
`drivers/<driver>.py` that reads it), and each per-layer metric's reader
in `metrics/<metric>.py`.
"""
