"""Dead lanes of the device batches (%): a burst of 200 in a 256-lane
shape is 22 %; a set's few cache misses in the same shape are more.

The reading is `dispatch_pad_share.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_pad_share.txset")(cell)
