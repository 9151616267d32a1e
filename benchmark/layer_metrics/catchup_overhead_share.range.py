"""Catchup outside ledger close (%): node start-up, download, both
checkpoints' parse, resolution and dispatch (the second's inside the
first's apply: `prefetch_ahead_ms.range`), result checks, shutdown.

The reading is `catchup_overhead_share`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("catchup_overhead_share")(cell)
