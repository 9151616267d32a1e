"""Per-signature verifies on the host per transaction replayed (us): the
checks apply made before their chunk had been adopted.

The reading is `host_verify_us_per_tx.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("host_verify_us_per_tx.catchup")(cell)
