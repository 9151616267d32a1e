"""Ledger layer, live closes: total of the program's
`ledger.close.applyTx` zone per transaction applied (us)."""


def read(cell):
    count, seconds = cell.zones.get("ledger.close.applyTx", (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not count or not txs:
        return None
    return seconds / txs * 1e6
