"""Per-signature verifies on the host per transaction replayed (us): 0.0
where the table answered every envelope and auth signature, more where
apply outran a chunk. This cell hands no SCP envelope over, so every
native verify counts.

The reading is `host_verify_us_per_tx.txset`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("host_verify_us_per_tx.txset")(cell)
