"""Share of apply's signature checks whose tuple the resolver never
made (%): the program's `crypto.prevalidated.miss.unknown` counter over
hit + miss. 0 where collection finds every signer; `correct` holds it
there."""


def read(cell):
    if "crypto.prevalidated.miss.unknown" not in cell.counters:
        return None
    unknown, _ = cell.counters.get("crypto.prevalidated.miss.unknown")
    hits, _ = cell.counters.get("crypto.prevalidated.hit", (0, 0.0))
    misses, _ = cell.counters.get("crypto.prevalidated.miss", (0, 0.0))
    if not hits + misses:
        return None
    return 100.0 * unknown / (hits + misses)
