#!/bin/sh
# The runs the bounds are set from: for each cell named, two sets of six
# runs on the same six seeds, then three traced runs on seeds of their
# own. A cell whose first run fails or reads not correct gets no more
# runs. One JSON line per run in $OUT/sets_<cell>.jsonl (default
# chipbench_out). SETS names the sets (default "a b"), TRACED the number
# of traced runs (default 3), SEED0 the base of the seeds.
set -u
OUT=${OUT:-chipbench_out}
mkdir -p $OUT
SECS=${SECS:-45}
SETS=${SETS:-a b}
TRACED=${TRACED:-3}
SEED0=${SEED0:-2147490000}
one() {  # cell set seed trace
  python3 chipbench/run.py --workload $1 --seed $3 --seconds $SECS --trace $4 > $OUT/run.out 2> $OUT/run.err
  rc=$?
  line=$(tail -n 1 $OUT/run.out)
  grep -E '^setup (devices|window)' $OUT/run.err | tr '\n' ' ' 
  echo "{\"cell\": \"$1\", \"set\": \"$2\", \"seed\": $3, \"rc\": $rc, \"compiles\": \"$(grep -o 'window: [0-9]*' $OUT/run.err)\", \"line\": ${line:-null}}" >> $OUT/sets_$1.jsonl
  echo "$1 $2 $3 rc=$rc $(echo "$line" | cut -c1-700)"
  [ $rc -eq 0 ] && echo "$line" | grep -q '"correct": true'
}
for w in "$@"; do
  : > $OUT/sets_$w.jsonl
  ok=1
  for set in $SETS; do
    for s in 1 2 3 4 5 6; do
      [ $ok -eq 1 ] || continue
      one $w $set $((SEED0 + s * 7919)) 0 || { ok=0; tail -n 30 $OUT/run.err; }
    done
  done
  for s in $(seq 1 $TRACED); do
    [ $ok -eq 1 ] || continue
    one $w traced $((SEED0 + 5000 + s * 104729)) 1 || { ok=0; tail -n 30 $OUT/run.err; }
  done
done
