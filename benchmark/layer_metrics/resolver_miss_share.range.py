"""Share of apply's signature checks whose tuple the resolver never
made (%), over both checkpoints of a replay: 0 where the second
checkpoint's signers are resolved from what the first has in flight
(`correct` holds it there); a resolver that knows only the node's state
and the checkpoint's own operations reads the second checkpoint's
non-master signatures here.

The reading is `resolver_miss_share.dense`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("resolver_miss_share.dense")(cell)
