"""Dispatch to first collect of a device run (ms): mean of the program's
`crypto.verify.dispatch.wall` timer: 25 runs of the 256-lane shape a
ledger, each collected by the crank that dispatched it.

The reading is `dispatch_wall_ms.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_wall_ms.txset")(cell)
