"""What a replayed close stands still for the previous ledger's tail
(ms), over both checkpoints of a replay.

The reading is `complete_wait_ms.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("complete_wait_ms.catchup")(cell)
