"""Herder admission from inside: total of the program's
`herder.recvTransaction` zone per call (us). `admit_us_per_tx.live`
times the same calls from outside, the benchmark's loop included."""


def read(cell):
    count, seconds = cell.zones.get("herder.recvTransaction", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e6
