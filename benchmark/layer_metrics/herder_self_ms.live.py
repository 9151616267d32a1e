"""The herder's own time per close (ms): total of the program's
`herder.triggerNextLedger` zone less the `ledger.closeLedger` and
`herder.joinCompletion` zones nested in it, per trigger: trimming the
queue, building the set, proposing upgrades, queue upkeep."""


def read(cell):
    closes, whole = cell.zones.get("herder.triggerNextLedger", (0, 0.0))
    if not closes:
        return None
    _, ledger = cell.zones.get("ledger.closeLedger", (0, 0.0))
    _, tail = cell.zones.get("herder.joinCompletion", (0, 0.0))
    return (whole - ledger - tail) / closes * 1e3
