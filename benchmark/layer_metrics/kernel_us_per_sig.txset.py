"""Device time of the `verify_kernel_msg32` program per real signature (us):
two runs of the one 4,096-lane shape a set, the second 904 signatures
wide. Nothing in a window with no run (every signature cached).

The reading is `kernel_us_per_sig.catchup`'s, made by that reader, in the cell
`txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("kernel_us_per_sig.catchup")(cell)
