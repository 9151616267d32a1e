"""Share of the measured window in which no operation ran on the device
(%), where every decorated signature of a replay reaches it in chunks of
one bucket: below 50 the device would have most of the work.

The reading is `device_idle_share.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("device_idle_share.catchup")(cell)
