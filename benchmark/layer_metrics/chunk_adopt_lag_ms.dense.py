"""What a landed chunk waits for apply to look (ms): mean of the
program's `catchup.batch.adoptLag` timer, once an adopted chunk, from
the moment its verdicts landed to the start of the next ledger."""


def read(cell):
    n, seconds = cell.counters.get("catchup.batch.adoptLag", (0, 0.0))
    if not n:
        return None
    return seconds / n * 1e3
