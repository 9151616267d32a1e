"""Share of apply's signature checks whose chunk had not been adopted yet
(%): what apply outran, and verified natively.

The reading is `pending_miss_share.dense`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("pending_miss_share.dense")(cell)
