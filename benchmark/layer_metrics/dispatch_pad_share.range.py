"""Lanes of the dispatched buckets that carried padding (%): each
checkpoint's last chunk is not full, two of a replay's four.

The reading is `dispatch_pad_share.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_pad_share.catchup")(cell)
