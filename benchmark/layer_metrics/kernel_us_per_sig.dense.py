"""Device time of the `verify_kernel_msg32` program per real signature
(us), over the chunks (runs of the one 65,536-lane shape) that lie
inside the window.

The reading is `kernel_us_per_sig.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("kernel_us_per_sig.catchup")(cell)
