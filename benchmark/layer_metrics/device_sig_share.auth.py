"""Signatures that reached the device over the envelope and auth-entry
signatures of the checkpoints reached (%): 100 when every one of both
kinds became a tuple of the batch.

The reading is `device_sig_share.dense`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("device_sig_share.dense")(cell)
