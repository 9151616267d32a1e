"""Call to landed of a watched collect (ms): mean of the program's
`crypto.verify.dispatch.collectWait` timer, once a supervised collect
under a deadline: what `VERIFY_DISPATCH_DEADLINE_MS` is held against. A
chunk enqueued behind others waits for their runs too. A program without
the timer reports nothing."""


def read(cell):
    n, seconds = cell.counters.get("crypto.verify.dispatch.collectWait",
                                   (0, 0.0))
    if not n:
        return None
    return seconds / n * 1e3
