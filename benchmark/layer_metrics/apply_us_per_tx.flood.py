"""Ledger layer, followed closes: the `ledger.close.applyTx` zone per
transaction applied (us), at 5,000 payments a ledger.

The reading is `apply_us_per_tx.live`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("apply_us_per_tx.live")(cell)
