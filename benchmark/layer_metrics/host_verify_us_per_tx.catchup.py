"""Per-signature verifies on the host: sum of the program's
`crypto.verify.native` zone per transaction replayed (us): the
ledgers applied before the device batch landed."""


def read(cell):
    count, seconds = cell.zones.get("crypto.verify.native", (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not count or not txs:
        return None
    return seconds / txs * 1e6
