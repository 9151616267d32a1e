"""`txset_controls.py`'s manner, for the cell `soroban-auth.auth-replay`:
the two things only that deployment has, broken as a later PR might be
tempted to break them. A run under either control must come out not
correct. Used by test_soroban_cell.py at tiny size on the CPU, and at
the cell's own size on the chip:

    python benchmark/tests/soroban_controls.py --control \
        soroban.host_never_sees_the_table --workload \
        soroban-auth.auth-replay --seed 11 --seconds 30 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def host_never_sees_the_table(driver) -> None:
    """`ApplyContext.verify` stays `None`, the program's behaviour
    before the seam was closed: the Soroban host verifies every auth
    signature natively and the device's verdicts on them are thrown
    away. The chain, the accounts and the nonces stay right; the
    counters say what happened."""
    from stellar_core_tpu.tx import frame

    class Blind(frame.ApplyContext):
        verify = property(lambda self: None, lambda self, value: None)
    frame.ApplyContext = Blind


class _Settled:
    """A device verifier whose batch has landed, every chunk of it,
    when the dispatch returns; `verdict` rewrites what it says."""

    def __init__(self, inner, verdict=None):
        self._inner = inner
        self._verdict = verdict

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def verify_tuples_async(self, items):
        from stellar_core_tpu.ops import chunking
        landed = list(chunking.chunks_of(
            self._inner.verify_tuples_async(items), len(items)))
        if self._verdict is not None:
            landed = [(lo, hi, None if vs is None
                       else [self._verdict] * (hi - lo))
                      for lo, hi, vs in landed]
        return _Landed(landed)

    def verify_tuples(self, items):
        return self.verify_tuples_async(items)()


class _Landed:
    def __init__(self, landed):
        self._landed = landed

    def chunks(self):
        yield from self._landed

    def __call__(self):
        return [v for _, _, vs in self._landed for v in vs]


def _under_every_replay(driver, verdict) -> None:
    wrap = driver.wrap_verifier

    class Holder:
        pass

    def wrapped(verifier):
        holder = Holder()
        holder._inner = _Settled(verifier._inner, verdict)
        return wrap(holder)
    driver.wrap_verifier = wrapped


def settled(driver) -> None:
    """No control: every replay's batch has landed when its dispatch
    returns, as on a device that is faster than apply. The CPU
    rehearsal lays it under each control, because a 16-lane program on
    the CPU is slower than apply and everything it answers would be a
    counted pending miss, which is exact and hides both faults."""
    _under_every_replay(driver, None)


def table_says_true(driver) -> None:
    """Every replay's device verifier answers `True` to every tuple
    without looking (and has answered when the dispatch returns, so
    that apply asks the table and not the fallback): the bit-flipped
    transfers succeed, funds move that nobody authorized, and the
    chain forks from the publisher's."""
    _under_every_replay(driver, True)


# hooks that run after set-up (`driver_hook` of the harness)
CONTROLS = {
    "soroban.host_never_sees_the_table": host_never_sees_the_table,
    "soroban.table_says_true": table_says_true,
}

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    argv = sys.argv[1:]
    at = argv.index("--control")
    hook = CONTROLS[argv[at + 1]]
    del argv[at:at + 2]
    sys.exit(main(argv, t0=T0, root=ROOT, driver_hook=hook))
