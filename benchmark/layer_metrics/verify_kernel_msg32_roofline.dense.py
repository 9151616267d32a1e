"""Share of its roofline the `verify_kernel_msg32` program reaches (%) at
the work this cell gives it: seven runs a replay of the one shape, two
in flight. No kernel is new: the same `kernel_costs/verify_kernel_msg32.py`.

The reading is `verify_kernel_msg32_roofline`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("verify_kernel_msg32_roofline")(cell)
