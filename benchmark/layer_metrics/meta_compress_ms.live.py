"""Ledger layer, live closes: what a checkpoint ledger's tail waits
for the debug-meta segment (ms): mean of the program's
`ledger.close.meta.compress` zone. A program that gzips the whole
segment there reads the gzip; one that compresses beside the closes
reads the wait for the compressor's last record and the rename."""


def read(cell):
    count, seconds = cell.zones.get("ledger.close.meta.compress", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
