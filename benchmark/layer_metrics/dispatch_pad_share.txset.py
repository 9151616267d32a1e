"""Dead lanes of the device batches: sum of the program's
`crypto.verify.dispatch.padding` histogram / bucket lanes (%): 5,000
signatures as 4,096 and 904 in two runs of 4,096 lanes is 39 %. 0 in a
window that dispatched nothing."""


def read(cell):
    if "crypto.verify.dispatch.padding" not in cell.counters:
        return None
    _, padding = cell.counters["crypto.verify.dispatch.padding"]
    _, batch = cell.counters.get("crypto.verify.dispatch.batch", (0, 0.0))
    lanes = batch + padding
    return 100.0 * padding / lanes if lanes > 0 else 0.0
