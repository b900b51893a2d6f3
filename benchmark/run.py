"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see benchmark/README.md).
"""

import os
import sys
import time

T_START = time.perf_counter()  # set-up is counted from here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness.main import main

    sys.exit(main(sys.argv[1:], T_START))
