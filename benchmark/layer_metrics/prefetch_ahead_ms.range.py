"""What a checkpoint's apply stands still for the next checkpoint (ms):
the program's `catchup.prefetch.ahead` zone seconds over its count, once
a checkpoint that was parsed, resolved, packed and dispatched from the
crank of the one before it. A program without the zone reports
nothing."""


def read(cell):
    count, seconds = cell.zones.get("catchup.prefetch.ahead", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
