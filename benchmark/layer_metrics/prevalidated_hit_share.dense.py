"""Share of apply's signature checks that an adopted chunk answered (%).

The reading is `prevalidated_hit_share.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("prevalidated_hit_share.catchup")(cell)
