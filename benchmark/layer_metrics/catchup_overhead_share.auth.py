"""Catchup outside ledger close (%): node start-up, download, parsing, the
collection of envelope and auth tuples before the first dispatch, result
checks, shutdown.

The reading is `catchup_overhead_share`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("catchup_overhead_share")(cell)
