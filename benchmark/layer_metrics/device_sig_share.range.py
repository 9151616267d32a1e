"""Signatures that reached the device over the decorated signatures of
the checkpoints whose batch a replay dispatched (%): the driver counts
them a checkpoint, two a replay.

The reading is `device_sig_share.dense`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("device_sig_share.dense")(cell)
