"""Share of the measured window in which no operation ran on the device
(%): four runs of the one 65,536-lane shape a replay.

The reading is `device_idle_share.catchup`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("device_idle_share.catchup")(cell)
