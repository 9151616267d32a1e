"""Herder layer, the queue's upkeep after a peer's set closes (ms a
ledger): seconds of the program's `herder.ledgerClosed` zone over its
count: `remove_applied` and `shift` over a queue that holds the set's
5,000 frames, the per-transaction latency samples, the counters'
publication. Inside `scp_self_ms.flood`. Nothing on a program without
the zone or in a window without a close."""


def read(cell):
    count, seconds = cell.zones.get("herder.ledgerClosed", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
