"""What a live close's apply stood still, per transaction applied (us),
with the previous ledger's completion tail beside it.

The reading is `apply_wait_us_per_tx.replay`'s, made by that reader, in
the cell `standalone-pay1000.closed`."""


def read(cell):
    return cell.spec.layer_reader("apply_wait_us_per_tx.replay")(cell)
