#!/bin/bash
# The runs a new cell's numbers in PERF.md come from, in one chip call:
#
#   chiprun --timeout 2400 -- bash <checkout>/tools/cell_runs.sh <cell> <first seed> <n> [<other checkout>]
#
# from the checkout this file lies in (the working tree, or an unpacked
# `git archive $(git write-tree)` under _checkout/): <n> untraced runs of
# `benchmarks/run.py --seconds <run_seconds>` on seeds <first seed>.., one
# traced run on the next seed, and, where another checkout is named (the parent
# commit with this tree's BENCHMARK.json and benchmarks/ laid over it), one run
# of the cell there, which must end at once if the parent cannot run it. Logs and
# one summary line a run go to chiprun_out/cell_runs/<cell>/ of the directory
# it was called from.
set -u
cell=$1; first=$2; n=$3; other=${4:-}
here=$(cd "$(dirname "$0")/.." && pwd)
out=$(pwd)/chiprun_out/cell_runs/$cell
mkdir -p "$out"
[ -n "$other" ] && other=$(cd "$other" && pwd)
seconds=$(python3 -c "import json; print(json.load(open('$here/BENCHMARK.json'))['run_seconds'])")
one() {  # <checkout> <seed> <trace> <log>
  (cd "$1" && timeout 900 python3 benchmarks/run.py --workload "$cell" --seed "$2" \
      --seconds "$seconds" --trace "$3" > "$4" 2>&1; echo "rc=$? seed=$2 trace=$3 in $1" >> "$4")
  { grep -h "^check " "$4"; tail -n 2 "$4"; } | tee -a "$out/summary.log"
}
for i in $(seq 0 $((n - 1))); do one "$here" $((first + i)) 0 "$out/run_$((first + i)).log"; done
one "$here" $((first + n)) 1 "$out/traced_$((first + n)).log"
[ -n "$other" ] && one "$other" $((first + n + 1)) 0 "$out/other_checkout.log"
exit 0
