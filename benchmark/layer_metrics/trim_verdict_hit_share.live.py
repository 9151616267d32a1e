"""Herder layer: share of the frames the proposer's trim walked that it
kept on the verdict they carried (%): `herder.trim.verdict.hit` over
`.hit` + `.miss` inside the window. A hit is a frame admitted against
the LCL its set is built on, kept without a second `check_valid`; a
miss is one the trim validated (no verdict, another LCL, another
sequence number, a kind that carries none). Nothing on a program
without the counters, and nothing where the window trimmed no frame."""


def read(cell):
    hit, _ = cell.counters.get("herder.trim.verdict.hit", (0, 0.0))
    miss, _ = cell.counters.get("herder.trim.verdict.miss", (0, 0.0))
    return 100.0 * hit / (hit + miss) if hit + miss else None
