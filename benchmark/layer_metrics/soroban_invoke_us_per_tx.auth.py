"""Ledger layer, the Soroban host: one `invoke_host_function` (us), the
program's `soroban.invoke` zone seconds over its count: budget,
footprint checks, the auth match, the contract's own work, the event.
Nothing on a program without the zone; 0.0 at a count of 0."""


def read(cell):
    if "soroban.invoke" not in cell.zones:
        return None
    count, seconds = cell.zones["soroban.invoke"]
    return seconds / count * 1e6 if count else 0.0
