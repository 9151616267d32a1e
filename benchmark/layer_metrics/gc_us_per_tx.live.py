"""The collector's seconds per transaction applied by the live node (us).

The reading is `gc_us_per_tx.replay`'s, made by that reader, in the cell
`standalone-pay1000.closed`."""


def read(cell):
    return cell.spec.layer_reader("gc_us_per_tx.replay")(cell)
