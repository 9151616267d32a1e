"""Share of its roofline the `verify_kernel_msg32` program reaches (%) at
the 256-lane shape. No kernel is new: the accepted
`kernel_costs/verify_kernel_msg32.py`, which reckons the least time
from the lanes a run has (batch and padding), not from its signatures.

The reading is `verify_kernel_msg32_roofline`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("verify_kernel_msg32_roofline")(cell)
