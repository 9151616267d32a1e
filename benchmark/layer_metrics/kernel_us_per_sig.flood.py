"""Device time of the `verify_kernel_msg32` program per real signature
(us) at the 256-lane shape a burst of 200 runs on.

The reading is `kernel_us_per_sig.catchup`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("kernel_us_per_sig.catchup")(cell)
