"""Share of apply's signature checks that the device batch answered
(%): the program's `crypto.prevalidated.hit` counter over hit + miss.
`device_sig_share.catchup` says what went to the device; this says
what came back in time to be used."""


def read(cell):
    hits, _ = cell.counters.get("crypto.prevalidated.hit", (0, 0.0))
    misses, _ = cell.counters.get("crypto.prevalidated.miss", (0, 0.0))
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
