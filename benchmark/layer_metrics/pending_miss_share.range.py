"""Share of apply's signature checks whose tuple was dispatched and
whose chunk had not been adopted yet (%), over both checkpoints of a
replay: all of it is the cold first checkpoint's, for the second one's
verdicts are back before its first ledger.

The reading is `pending_miss_share.dense`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("pending_miss_share.dense")(cell)
