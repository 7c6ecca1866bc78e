#!/bin/bash
# Pairs of one cell on two checkouts in one chip call, sides alternating:
#
#   chiprun --timeout 3000 -- bash tools/pair_runs.sh <cell> <first seed> <pairs> <parent checkout> <change checkout>
#
# pair i runs seed <first seed>+i on both sides, parent first on even i and
# change first on odd i (parent, change, change, parent, ...), untraced, at
# BENCHMARK.json's run_seconds. Logs and one result line a run go to
# chiprun_out/pair_runs/<cell>/ of the directory it was called from.
set -u
cell=$1; first=$2; pairs=$3; parent=$(cd "$4" && pwd); change=$(cd "$5" && pwd)
out=$(pwd)/chiprun_out/pair_runs/$cell
mkdir -p "$out"
seconds=$(python3 -c "import json; print(json.load(open('$change/BENCHMARK.json'))['run_seconds'])")
one() {  # <side> <checkout> <seed>
  log="$out/$1_$3.log"
  (cd "$2" && timeout 900 python3 benchmarks/run.py --workload "$cell" --seed "$3" \
      --seconds "$seconds" --trace 0 > "$log" 2>&1; echo "rc=$?" >> "$log")
  echo "$1 seed=$3 $(grep -h '^{"correct"' "$log" | tail -n 1) $(tail -n 1 "$log")" | tee -a "$out/summary.log"
}
for i in $(seq 0 $((pairs - 1))); do
  seed=$((first + i))
  if [ $((i % 2)) -eq 0 ]; then one parent "$parent" $seed; one change "$change" $seed
  else one change "$change" $seed; one parent "$parent" $seed; fi
done
exit 0
