"""Share of apply's signature checks that an adopted chunk answered (%),
over both checkpoints of a replay.

The reading is `prevalidated_hit_share.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("prevalidated_hit_share.catchup")(cell)
