"""Lanes of the dispatched buckets that carried padding (%): only the last
chunk of a split batch is not full.

The reading is `dispatch_pad_share.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_pad_share.catchup")(cell)
