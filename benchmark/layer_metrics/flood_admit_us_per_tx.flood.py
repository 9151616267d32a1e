"""Herder layer, batched flood admission: what one flooded transaction
costs the crank (us): seconds of the program's `herder.recvTransactions`
zone (one hit a burst: the tuples, the verify service's batch and the
wait for it, `try_add` of every frame against the ledger root) over the
frames the bursts admitted (`herder.flood.admitted`). Set beside
`herder_admit_us_per_tx.live`, the same admission one at a time with a
native verify each. Nothing on a program without the zone; 0.0 where
nothing was admitted."""


def read(cell):
    if "herder.recvTransactions" not in cell.zones:
        return None
    _, seconds = cell.zones["herder.recvTransactions"]
    admitted, _ = cell.counters.get("herder.flood.admitted", (0, 0.0))
    return seconds / admitted * 1e6 if admitted else 0.0
