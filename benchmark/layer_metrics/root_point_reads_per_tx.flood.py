"""Ledger layer: lookups of the ledger root that neither its entry
cache nor a close's prefetch answered and that went to a point SELECT
of the SQL store, per transaction applied: the program's counter
`ledger.root.point.sql` (published once a close; what admission reads
between two closes is in the next close's) over the transactions
applied. Admission's `try_add` reads the source account of every
flooded frame with no prefetch, and 5,000 accounts do not fit the
root's 4,096-entry cache. Nothing on a program without the counter;
0.0 where nothing was applied."""


def read(cell):
    if "ledger.root.point.sql" not in cell.counters:
        return None
    reads, _ = cell.counters["ledger.root.point.sql"]
    txs = cell.traffic_counts.get("transactions")
    return reads / txs if txs else 0.0
