"""Herder layer: what the crank stands still for a burst's verdicts,
per frame received (us): seconds of the program's
`herder.recvTransactions.verify` zone (`submit_many` to the last
verdict: packing, the transfer, one run of the 256-lane program, the
collect) over `herder.flood.received`. `flood_admit_us_per_tx.flood`
less this is the queue's and the ledger root's share. Nothing on a
program without the zone; 0.0 where nothing was received."""


def read(cell):
    if "herder.recvTransactions.verify" not in cell.zones:
        return None
    _, seconds = cell.zones["herder.recvTransactions.verify"]
    received, _ = cell.counters.get("herder.flood.received", (0, 0.0))
    return seconds / received * 1e6 if received else 0.0
