"""Signer resolution of one checkpoint (ms), mean of a replay's two: the
second runs inside the first checkpoint's apply, with the carried
signer keys.

The reading is `collect_tuples_ms.dense`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("collect_tuples_ms.dense")(cell)
