"""Herder and SCP, their own time a flooded and followed ledger (ms):
envelope signatures, the ballot protocol, the node's own statements, and
the upkeep of a queue that holds the set's 5,000 frames
(`queue_upkeep_ms.flood`).

The reading is `scp_self_ms.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("scp_self_ms.txset")(cell)
