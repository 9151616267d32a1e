"""Dead lanes of the device batches: sum of the program's
`crypto.verify.dispatch.padding` histogram / bucket lanes (%)."""


def read(cell):
    n, padding = cell.counters.get("crypto.verify.dispatch.padding",
                                   (0, 0.0))
    _, batch = cell.counters.get("crypto.verify.dispatch.batch", (0, 0.0))
    if not n or batch + padding <= 0:
        return None
    return 100.0 * padding / (batch + padding)
