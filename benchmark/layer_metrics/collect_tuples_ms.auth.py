"""Collection of one checkpoint's tuples (ms), envelope signatures and
auth-entry signatures in one pass.

The reading is `collect_tuples_ms.dense`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("collect_tuples_ms.dense")(cell)
