"""`multisig_controls.py`'s manner, for the cell
`multisig-range.range-replay`: the one thing only that deployment has,
broken as a later PR might be tempted to break it. A run under the
control must come out not correct. Used by test_range_cell.py at tiny
size on the CPU, and at the cell's own size on the chip:

    python benchmark/tests/range_controls.py --control \
        range.resolver_drops_carried --workload multisig-range.range-replay \
        --seed 11 --seconds 30 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resolver_drops_carried(driver) -> None:
    """Collection resolves a checkpoint against the node's state and
    the checkpoint's own operations only (the resolver as it was before
    this deployment): a checkpoint collected while the one before it
    applies misses every signer that one installs or rotates in."""
    from stellar_core_tpu.catchup import catchup_work
    real = catchup_work.collect_signature_tuples

    def without_carry(frames, network_id=None, **kw):
        kw.pop("carried", None)
        return real(frames, network_id, **kw)
    catchup_work.collect_signature_tuples = without_carry


CONTROLS = {
    "range.resolver_drops_carried": resolver_drops_carried,
}

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    argv = sys.argv[1:]
    at = argv.index("--control")
    hook = CONTROLS[argv[at + 1]]
    del argv[at:at + 2]
    sys.exit(main(argv, t0=T0, root=ROOT, driver_hook=hook))
