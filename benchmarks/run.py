"""``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``"""

import time

T_START = time.perf_counter()  # set-up is counted from the command's first line

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
