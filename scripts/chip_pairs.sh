#!/bin/bash
# The benchmark's cells on the parent (unpacked into _parent/) and on this tree in turn, one
# process a run, for a builder's own chip call:
#   chiprun --timeout 3000 -- bash scripts/chip_pairs.sh <out> <trace> <cell>:<seed> ...
# For each cell: parent, change on <seed>, then change, parent on <seed> + 1. The result lines and
# each run's run.json land under chiprun_out/<out>/. CHANGE=<dir> runs another checkout as the
# change (the committed files alone, unpacked into _final/); ONE=1 runs the first pair of a cell alone.
out=$1; trace=$2; shift 2
change=${CHANGE:-.}
mkdir -p chiprun_out/$out
run() {  # checkout label cell seed
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 10 --trace $trace 2>/dev/null | tail -n 1) > chiprun_out/$out/$2.$3.$4.json
  mkdir -p chiprun_out/$out/runs.$2 && cp -r $1/chiprun_out/benchmarks/$3 chiprun_out/$out/runs.$2/ 2>/dev/null
  python3 - <<PY
import json
try:
    d = json.load(open("chiprun_out/$out/$2.$3.$4.json"))
    print("$2 $3 $4", d.get("correct"), {k: round(v["value"], 4) for k, v in d["metrics"].items()
                                          if k in ("tokens_per_s_chip", "step_hbm_gib", "setup_s")}, flush=True)
except Exception as e:
    print("$2 $3 $4 unreadable:", e, flush=True)
PY
}
for pair in "$@"; do
  cell=${pair%%:*}; seed=${pair##*:}
  run _parent parent $cell $seed
  run $change change $cell $seed
  [ -n "$ONE" ] && continue
  run $change change $cell $((seed + 1))
  run _parent parent $cell $((seed + 1))
done
