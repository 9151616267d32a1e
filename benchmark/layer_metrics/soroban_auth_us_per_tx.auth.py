"""Ledger layer, the Soroban host: the check of one address-credential
entry (us), the program's `soroban.auth` zone seconds (expiration,
signer, the signature's verdict, the nonce entry and its TTL entry
created) over `soroban.auth.entries.address`. `soroban_invoke_us_per_tx.
auth` less this is the host without the mechanism. Nothing on a
program without the host's zones; 0.0 where no entry carried address
credentials (the program reports no `soroban.auth` at a count of 0)."""


def read(cell):
    if "soroban.invoke" not in cell.zones:
        return None
    _, seconds = cell.zones.get("soroban.auth", (0, 0.0))
    entries, _ = cell.counters.get("soroban.auth.entries.address",
                                   (0, 0.0))
    return seconds / entries * 1e6 if entries else 0.0
