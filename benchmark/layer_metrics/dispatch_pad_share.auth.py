"""Lanes of the dispatched buckets that carried padding (%): only the
last of a replay's 26 chunks is not full.

The reading is `dispatch_pad_share.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_pad_share.catchup")(cell)
