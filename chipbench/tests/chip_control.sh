#!/bin/sh
# The readings the limits of `correct` were set from, at the cells' size
# (PERF.md §2): the program on a dozen seeds or more, the control and the
# faults on three. One JSON line per reading in $OUT (default
# chipbench_out). Cells: the arguments, or both.
set -u
OUT=${OUT:-chipbench_out}
mkdir -p $OUT
seeds() { python3 -c "print(','.join(str($1 + i) for i in range($2)))"; }
for w in ${*:-train-steady fleet-chaos}; do
  case $w in
    train-*) python3 chipbench/control.py --workload $w \
      --seeds "$(seeds 2147483901 12),$(seeds 2147484101 20)" \
      --control-seeds "$(seeds 2147483921 3)" > $OUT/control_$w.jsonl ;;
    *) python3 chipbench/control.py --workload $w \
      --seeds "$(seeds 2147484001 12)" \
      --control-seeds "$(seeds 2147484021 3)" > $OUT/control_$w.jsonl ;;
  esac
done
