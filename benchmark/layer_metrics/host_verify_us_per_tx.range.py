"""Per-signature verifies on the host per transaction replayed (us): the
checks the first checkpoint's apply made before its chunks were
adopted; the second checkpoint should add none.

The reading is `host_verify_us_per_tx.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("host_verify_us_per_tx.catchup")(cell)
