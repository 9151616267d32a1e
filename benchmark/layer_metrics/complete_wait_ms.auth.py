"""What a replayed close stands still for the previous ledger's tail (ms),
with contract ledgers in both.

The reading is `complete_wait_ms.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("complete_wait_ms.catchup")(cell)
