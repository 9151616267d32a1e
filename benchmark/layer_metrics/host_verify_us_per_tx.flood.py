"""Crypto layer: per-signature verifies of transaction signatures on the
host, per transaction applied (us), beyond the SCP envelopes' own: 0.0
where the bursts' device batches and the verify cache answered every
one, which `correct` holds.

The reading is `host_verify_us_per_tx.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("host_verify_us_per_tx.txset")(cell)
