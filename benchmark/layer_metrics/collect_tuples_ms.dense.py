"""Signer resolution of one checkpoint (ms): the program's
`crypto.collectTuples` zone seconds over its count, on the closing
thread before the batch is dispatched."""


def read(cell):
    count, seconds = cell.zones.get("crypto.collectTuples", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
