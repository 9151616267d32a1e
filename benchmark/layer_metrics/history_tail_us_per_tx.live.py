"""Ledger layer, live closes: what follows every close on the
completion worker, per transaction applied (us). Totals of the
program's `ledger.close.complete.encode` (the one encoding pass; a
program without the zone counts 0 there, its encoding being inside the
next two), `ledger.close.meta` and `ledger.close.txHistory` zones, less
`ledger.close.meta.compress` (the checkpoint ledger's one gzip, inside
`meta`)."""


def read(cell):
    closes, history = cell.zones.get("ledger.close.txHistory", (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not closes or not txs:
        return None
    _, encode = cell.zones.get("ledger.close.complete.encode", (0, 0.0))
    _, meta = cell.zones.get("ledger.close.meta", (0, 0.0))
    _, gzip = cell.zones.get("ledger.close.meta.compress", (0, 0.0))
    return (encode + meta + history - gzip) / txs * 1e6
