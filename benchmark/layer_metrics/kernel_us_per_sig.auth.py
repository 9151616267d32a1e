"""Device time of the `verify_kernel_msg32` program per real signature
(us): an auth payload is a 32-byte SHA-256, so both kinds of tuple take
the one kernel and the one shape.

The reading is `kernel_us_per_sig.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("kernel_us_per_sig.catchup")(cell)
