"""Share of the measured window in which no operation ran on the device
(%), where a replayed ledger is four payments' apply and 1,800 tuples.

The reading is `device_idle_share.catchup`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("device_idle_share.catchup")(cell)
