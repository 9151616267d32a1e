"""Ledger layer, replayed closes: what a close stands still for the
previous ledger's completion tail (ms): mean of the program's
`ledger.close.completeWait` zone, once a close. A program that joins
the tail at the close's first statement reads the whole tail here; one
that joins it before `seal` reads what of the tail outlasted the
next close's apply."""


def read(cell):
    count, seconds = cell.zones.get("ledger.close.completeWait", (0, 0.0))
    if not count:
        return None
    return seconds / count * 1e3
