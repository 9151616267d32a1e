"""What a followed close stands still for the previous ledger's completion
tail (ms): the next slot's messages follow the commit at once, so the
tail of 5,000 rows runs beside the next set's validation and apply.

The reading is `complete_wait_ms.catchup`'s, made by that reader, in the cell
`txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("complete_wait_ms.catchup")(cell)
