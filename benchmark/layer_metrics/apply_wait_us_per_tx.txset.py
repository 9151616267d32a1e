"""What a followed close's apply stood still, per transaction applied
(us): the `ledger.close.applyTx` zone less its on-CPU seconds, with the
previous ledger's completion tail of 5,000 rows beside it.

The reading is `apply_wait_us_per_tx.replay`'s, made by that reader, in
the cell `txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("apply_wait_us_per_tx.replay")(cell)
