"""Bucket list and database commit of a 5,000-payment ledger: mean of the
`ledger.close.seal` zone (ms a close).

The reading is `seal_ms.live`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("seal_ms.live")(cell)
