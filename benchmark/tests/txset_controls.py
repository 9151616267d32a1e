"""`range_controls.py`'s manner, for the cell `txset-5000.validate`: the
two things only that deployment has, broken as a later PR might be
tempted to break them. A run under either control must come out not
correct. Used by test_txset_cell.py at tiny size on the CPU, and at the
cell's own size on the chip:

    python benchmark/tests/txset_controls.py --control \
        txset.prevalidator_says_true --workload txset-5000.validate \
        --seed 11 --seconds 30 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def prevalidator_says_true(driver) -> None:
    """The node's device verifier answers `True` to every tuple without
    looking (a batch that is never collected would read the same): the
    chain, the accounts and every count stay right, and the corrupted
    set is voted for."""
    inner = driver.app.batch_verifier

    class SaysTrue:
        def __getattr__(self, name):
            return getattr(inner, name)

        def verify_tuples(self, items):
            inner.verify_tuples(items)
            return [True] * len(items)
    driver.app.batch_verifier = driver.app.herder.batch_verifier = SaysTrue()


def cache_left_warm(driver) -> None:
    """The generator does not clear the process-wide verify cache after
    set-up: the publisher's native verdicts answer every signature and
    the node's batch is empty, as PR 27's cell read. Acts on the new
    driver, before its set-up."""
    driver.clear_cache = False


# hook that runs after set-up (`driver_hook` of the harness), and for a
# control that must act before it, what to do to the new driver
CONTROLS = {
    "txset.prevalidator_says_true": (prevalidator_says_true, None),
    "txset.cache_left_warm": (None, cache_left_warm),
}


def run_under(control: str, argv, t0: float, root: str, **kw) -> int:
    """`benchmark.harness.main.main` with the control laid on: after
    set-up through `driver_hook`, before it through the generator's
    `Driver.__init__`, which the harness calls with the cell alone."""
    from benchmark.harness.main import main
    from benchmark.harness.spec import Spec
    after, before = CONTROLS[control]
    if before is None:
        return main(argv, t0=t0, root=root, driver_hook=after, **kw)
    real = Spec.generator

    def generator(self, name):
        mod = real(self, name)
        init = mod.Driver.__init__

        def patched(drv, cell):
            init(drv, cell)
            before(drv)
        mod.Driver.__init__ = patched
        return mod
    Spec.generator = generator
    try:
        return main(argv, t0=t0, root=root, **kw)
    finally:
        Spec.generator = real


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    argv = sys.argv[1:]
    at = argv.index("--control")
    control = argv[at + 1]
    del argv[at:at + 2]
    sys.exit(run_under(control, argv, T0, ROOT))
