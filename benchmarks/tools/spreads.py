"""From the result lines of a cell's two sets of runs to what a bound is set
from: per metric each set's median and spread (quartile distance over the
median), the wider of the two, and how far the second median lies from the
first. Not part of a benchmark run.

    python3 benchmarks/tools/spreads.py set1_*.out -- set2_*.out
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import stats  # noqa: E402


def last_line(path: str) -> dict:
    with open(path) as f:
        lines = [l for l in f.read().splitlines() if l.startswith("{")]
    return json.loads(lines[-1])


def main(argv) -> int:
    cut = argv.index("--")
    sets = [[last_line(p) for p in argv[:cut]], [last_line(p) for p in argv[cut + 1:]]]
    names = sorted(sets[0][0]["metrics"])
    print("correct:", [[r["correct"] for r in s] for s in sets])
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s if name in r["metrics"]] for s in sets]
        med = [statistics.median(v) for v in vals]
        # the compiling first run of a set is recorded apart for setup_s
        spr = [stats.spread(v[1:] if name == "setup_s" else v) for v in vals]
        print(f"{name}: medians {med[0]:.6g} {med[1]:.6g} (second/first {med[1] / med[0] - 1:+.4%}) "
              f"spreads {spr[0]:.4%} {spr[1]:.4%} wider {max(spr):.4%}")
        for v in vals:
            print("   ", " ".join(f"{x:.6g}" for x in v))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
