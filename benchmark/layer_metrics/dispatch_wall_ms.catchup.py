"""Dispatch to first collect of a device batch: mean of the program's
`crypto.verify.dispatch.wall` timer (ms), over the batches collected
inside the window."""


def read(cell):
    n, seconds = cell.counters.get("crypto.verify.dispatch.wall", (0, 0.0))
    if not n:
        return None
    return seconds / n * 1e3
