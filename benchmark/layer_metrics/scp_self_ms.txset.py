"""Herder and SCP, their own time a followed ledger (ms): total of the
program's `herder.recvSCPEnvelope` zone less the `herder.txset.validate`
and `ledger.closeLedger` zones nested in it, over the ledgers followed:
envelope signatures, the fetch bookkeeping, the ballot protocol, the
node's own statements, queue upkeep after the close. Nothing on a
program without the validation zone."""


def read(cell):
    ledgers = cell.traffic_counts.get("ledgers")
    if "herder.txset.validate" not in cell.zones or not ledgers:
        return None
    _, whole = cell.zones.get("herder.recvSCPEnvelope", (0, 0.0))
    _, validate = cell.zones["herder.txset.validate"]
    _, close = cell.zones.get("ledger.closeLedger", (0, 0.0))
    return (whole - validate - close) / ledgers * 1e3
