"""Share of apply's signature checks whose tuple collection never made
(%); `correct` holds it at 0.

The reading is `resolver_miss_share.dense`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("resolver_miss_share.dense")(cell)
