"""Share of apply's signature checks, the envelope's and the Soroban
host's, that an adopted chunk answered (%).

The reading is `prevalidated_hit_share.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("prevalidated_hit_share.catchup")(cell)
