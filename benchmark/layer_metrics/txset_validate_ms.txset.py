"""Herder layer: one validation of a received set against the last closed
ledger (ms): the program's `herder.txset.validate` zone, seconds over
its count: `prepare_for_apply`, the tuples, the cache probes, the device
batch and its collect, the `check_valid` pass. Nothing on a program
without the zone; 0 where no set was validated."""


def read(cell):
    if "herder.txset.validate" not in cell.zones:
        return None
    count, seconds = cell.zones["herder.txset.validate"]
    return seconds / count * 1e3 if count else 0.0
