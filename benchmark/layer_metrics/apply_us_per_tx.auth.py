"""The `ledger.close.applyTx` zone per transaction replayed (us), where every
transaction is one contract invocation: budget, footprint, auth match,
nonce entry, two accounts, an event, a refund.

The reading is `apply_us_per_tx.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("apply_us_per_tx.catchup")(cell)
