"""Dispatch to first collect of a chunk (ms): with two chunks in flight,
about two runs of the kernel. The supervisor's deadline applies to it.

The reading is `dispatch_wall_ms.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("dispatch_wall_ms.catchup")(cell)
