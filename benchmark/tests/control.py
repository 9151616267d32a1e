"""Run one cell with the timed path broken by one of controls.py, at
the cell's own size, on the chip (the harness's look for a chip stays
on). `correct` must come out false.

    python benchmark/tests/control.py --control catchup.accept_all \
        --workload catchup-pay1000.replay --seed 11 --seconds 25 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    from benchmark.tests.controls import CONTROLS
    argv = sys.argv[1:]
    at = argv.index("--control")
    hook = CONTROLS[argv[at + 1]]
    del argv[at:at + 2]
    sys.exit(main(argv, t0=T0, root=ROOT, driver_hook=hook))
