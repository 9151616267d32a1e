"""What a chunk's dispatch costs the thread that makes it (ms): the
closing thread for a batch's first two chunks, `batch-resolve` for the
rest.

The reading is `dispatch_host_ms.catchup`'s, made by that reader, in the
cell `multisig-dense.dense-replay`, where the timer counts once a chunk."""


def read(cell):
    return cell.spec.layer_reader("dispatch_host_ms.catchup")(cell)
