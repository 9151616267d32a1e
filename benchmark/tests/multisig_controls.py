"""`controls.py`'s manner, for the cell `multisig-dense.dense-replay`:
two ways to break what only that deployment has, each standing for what
a later PR might be tempted to do. A run under either must come out not
correct. Used by test_multisig_cell.py at tiny size on the CPU, and at
the cell's own size on the chip:

    python benchmark/tests/multisig_controls.py --control \
        dense.chunks_out_of_order --workload multisig-dense.dense-replay \
        --seed 11 --seconds 30 --trace 0
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resolver_drops_non_master(driver) -> None:
    """Collection pairs a signature with its transaction's source key
    only (the resolver as it was before this deployment): every other
    signer's check is a miss the table was never told of."""
    from stellar_core_tpu.catchup import catchup_work

    def source_only(frames, network_id=None, **kw):
        tuples = []
        for frame in frames:
            src = bytes(frame.source_id.value)
            h = frame.contents_hash()
            for ds in frame.signatures:
                if bytes(ds.hint) == src[-4:]:
                    tuples.append((src, bytes(ds.signature), h))
        return tuples
    catchup_work.collect_signature_tuples = source_only


class _ChunksOutOfOrder:
    """Every chunk's verdicts are handed back under the next chunk's
    place (the last under the first's): right verdicts, wrong tuples."""

    def __init__(self, inner):
        self._inner = inner
        self.batches = inner.batches

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def verify_tuples_async(self, items):
        handle = self._inner.verify_tuples_async(items)

        def collect():
            landed = list(handle.chunks())
            if len(landed) < 2:
                return [v for _, _, vs in landed for v in vs]
            moved = landed[-1:] + landed[:-1]
            out = []
            for (lo, hi, _), (_, _, verdicts) in zip(landed, moved):
                pad = list(verdicts) + [True] * (hi - lo)
                out.extend(pad[:hi - lo])
            return out
        return collect

    def verify_tuples(self, items):
        return self.verify_tuples_async(items)()


def chunks_out_of_order(driver) -> None:
    real = driver.wrap_verifier
    driver.wrap_verifier = lambda v: _ChunksOutOfOrder(real(v))


CONTROLS = {
    "dense.resolver_drops_non_master": resolver_drops_non_master,
    "dense.chunks_out_of_order": chunks_out_of_order,
}

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    argv = sys.argv[1:]
    at = argv.index("--control")
    hook = CONTROLS[argv[at + 1]]
    del argv[at:at + 2]
    sys.exit(main(argv, t0=T0, root=ROOT, driver_hook=hook))
