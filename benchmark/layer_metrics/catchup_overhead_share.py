"""Catchup outside ledger close: 100 x (1 - total of the program's
`ledger.closeLedger` zone / window): node start-up, download, parsing
the checkpoint, packing the device batch, result checks, shutdown."""


def read(cell):
    count, seconds = cell.zones.get("ledger.closeLedger", (0, 0.0))
    window = cell.window[1] - cell.window[0]
    if not count or window <= 0:
        return None
    return 100.0 * (1.0 - seconds / window)
