"""Dispatch to first collect of a chunk (ms), mean of a replay's four:
the second checkpoint's chunks are enqueued behind the first's.

The reading is `dispatch_wall_ms.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_wall_ms.catchup")(cell)
