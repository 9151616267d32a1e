"""The close's history tail per transaction replayed (us), over both
checkpoints of a replay.

The reading is `history_tail_us_per_tx.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("history_tail_us_per_tx.catchup")(cell)
