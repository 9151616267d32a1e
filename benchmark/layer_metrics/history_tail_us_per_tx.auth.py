"""The close's history tail per transaction replayed (us), for transaction
meta that carries Soroban events and return values.

The reading is `history_tail_us_per_tx.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("history_tail_us_per_tx.catchup")(cell)
