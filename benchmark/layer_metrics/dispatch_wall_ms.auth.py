"""Dispatch to first collect of a chunk (ms), three in flight.

The reading is `dispatch_wall_ms.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_wall_ms.catchup")(cell)
