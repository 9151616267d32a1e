"""Dispatch to first collect of a chunk (ms): mean of the program's
`crypto.verify.dispatch.wall` timer over the chunks collected inside
the window: two a set, both dispatched before the first is collected, on
the crank thread. 0 in a window that dispatched nothing."""


def read(cell):
    if "crypto.verify.dispatch.wall" not in cell.counters:
        return None
    n, seconds = cell.counters["crypto.verify.dispatch.wall"]
    return seconds / n * 1e3 if n else 0.0
