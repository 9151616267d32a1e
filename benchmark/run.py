#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in this one process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

loads, warms up, measures for `--seconds`, checks what the timed path
produced, prints the result as the last line of stdout and exits 0.
Without a TPU (or with fewer chips than the cell asks for), or outside
a checkout of the program, it exits non-zero and prints no result.
"""

import os
import sys
import time

T0 = time.perf_counter()        # set-up is counted from here

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    if not os.path.isdir(os.path.join(ROOT, "stellar_core_tpu")):
        print("benchmark/run.py: no stellar_core_tpu/ beside benchmark/: "
              "the benchmark measures the program of its checkout",
              file=sys.stderr)
        sys.exit(3)
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    sys.exit(main(sys.argv[1:], t0=T0, root=ROOT))
