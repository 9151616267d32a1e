"""Share of apply's signature checks whose tuple was dispatched and
whose chunk had not been adopted yet (%): the program's
`crypto.prevalidated.miss.pending` counter over hit + miss: what apply
outran, and verified natively."""


def read(cell):
    if "crypto.prevalidated.miss.pending" not in cell.counters:
        return None
    pending, _ = cell.counters.get("crypto.prevalidated.miss.pending")
    hits, _ = cell.counters.get("crypto.prevalidated.hit", (0, 0.0))
    misses, _ = cell.counters.get("crypto.prevalidated.miss", (0, 0.0))
    if not hits + misses:
        return None
    return 100.0 * pending / (hits + misses)
