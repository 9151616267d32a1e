"""Share of the measured window in which no operation ran on the device
(%): two runs of 4,096 lanes a followed ledger.

The reading is `device_idle_share.live`'s, made by that reader, in the cell
`txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("device_idle_share.live")(cell)
