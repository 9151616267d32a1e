"""Bucket list and database commit: mean of the program's
`ledger.close.seal` zone (ms a close)."""


def read(cell):
    count, seconds = cell.zones.get("ledger.close.seal", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
