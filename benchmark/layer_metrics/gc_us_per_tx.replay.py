"""Ledger layer, replayed closes: the collector's seconds per transaction
applied (us): total of the program's `runtime.gc` zone, which a node's
recorder reports while it records (one `gc.callbacks` entry; generations
0 and 1, the automatic ones, whichever thread the collection ran on; a
full collection is somebody's call and is not in the zone). Apply clones
nine entries a payment. Nothing where the program reports no collection."""


def read(cell):
    count, seconds = cell.zones.get("runtime.gc", (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not count or not txs:
        return None
    return seconds / txs * 1e6
