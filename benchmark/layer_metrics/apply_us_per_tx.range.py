"""The `ledger.close.applyTx` zone per transaction replayed (us), over
both checkpoints: the first applies beside two batches in flight.

The reading is `apply_us_per_tx.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("apply_us_per_tx.catchup")(cell)
