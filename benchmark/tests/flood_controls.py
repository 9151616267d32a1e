"""`txset_controls.py`'s manner, for the cell `txset-5000-flood.flooded`:
the three things only that deployment has, broken as a later PR might
be tempted to break them. A run under any of them must come out not
correct. Used by test_flood_cell.py at tiny size on the CPU, and at the
cell's own size on the chip:

    python benchmark/tests/flood_controls.py --control \
        flood.service_says_true --workload txset-5000-flood.flooded \
        --seed 11 --seconds 30 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def service_says_true(driver) -> None:
    """The node's verify service answers `True` to everything: the
    batches still run and every count stays right, and the adversarial
    burst's flipped frames are admitted."""
    inner = driver.app.herder.verify_service

    class Yes:
        def __init__(self, future):
            self._future = future

        def result(self):
            self._future.result()
            return True

    class SaysTrue:
        def __getattr__(self, name):
            return getattr(inner, name)

        def submit_many(self, items):
            return [Yes(f) for f in inner.submit_many(items)]
    driver.app.herder.verify_service = SaysTrue()


def flood_skipped(driver) -> None:
    """The driver hands over the sets and the envelopes and no burst:
    that is `txset-5000.validate`, not this cell. Acts on the new
    driver, before its set-up."""
    driver.flood = False


def no_start_up_load(driver) -> None:
    """The node starts without loading its live shapes (switched off
    here, in the control only): the first burst meets its shape on the
    crank. Acts before set-up; the run that follows in this process
    ends with it."""
    from stellar_core_tpu.main.application import Application
    Application._load_verify_shapes = lambda self: None


# hook that runs after set-up (`driver_hook` of the harness), and for a
# control that must act before it, what to do to the new driver
CONTROLS = {
    "flood.service_says_true": (service_says_true, None),
    "flood.skipped": (None, flood_skipped),
    "flood.no_start_up_load": (None, no_start_up_load),
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
