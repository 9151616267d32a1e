"""Herder layer: what the crank stood still inside a set's validation
(ms a set): the `herder.txset.validate` zone less its on-CPU seconds
(the derived entry `herder.txset.validate.onCpu`): the wait for the
device's two runs and for the interpreter, which the previous ledger's
tail holds. The thread clock over-counts a contended thread, so this is
a lower bound (PERF.md section 6, PR 37). Nothing on a program without
the zone, or where not every hit carries on-CPU seconds."""


def read(cell):
    if "herder.txset.validate" not in cell.zones:
        return None
    count, wall = cell.zones["herder.txset.validate"]
    if not count:
        return 0.0
    measured, on_cpu = cell.zones.get("herder.txset.validate.onCpu",
                                      (0, 0.0))
    if measured != count:
        return None
    return (wall - on_cpu) / count * 1e3
