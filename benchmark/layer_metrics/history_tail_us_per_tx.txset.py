"""What follows every followed close on the completion worker, per
transaction applied (us): 5,000 history rows a ledger.

The reading is `history_tail_us_per_tx.live`'s, made by that reader, in the cell
`txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("history_tail_us_per_tx.live")(cell)
