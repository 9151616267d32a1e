"""Device time of the `verify_kernel_msg32` program per real signature
(us), over a replay's four runs of the one 65,536-lane shape.

The reading is `kernel_us_per_sig.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("kernel_us_per_sig.catchup")(cell)
