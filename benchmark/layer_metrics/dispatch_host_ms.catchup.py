"""What a dispatch costs the thread that makes it: mean of the
program's `crypto.verify.dispatch.host` timer (ms), entry of
`verify_tuples_async` to the return of the jit call."""


def read(cell):
    n, seconds = cell.counters.get("crypto.verify.dispatch.host", (0, 0.0))
    if not n:
        return None
    return seconds / n * 1e3
