"""Crypto layer: share of the collected tuples that are auth-entry
signatures (%): `crypto.collect.auth` over `crypto.collect.candidates`:
800 of a transfer ledger's 1,800 at the configuration's relayed share.
Nothing on a program without the counter; 0.0 where nothing was
collected."""


def read(cell):
    if "crypto.collect.auth" not in cell.counters:
        return None
    auth, _ = cell.counters["crypto.collect.auth"]
    tuples, _ = cell.counters.get("crypto.collect.candidates", (0, 0.0))
    return 100.0 * auth / tuples if tuples else 0.0
