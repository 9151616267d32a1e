"""Herder layer: one validation of a received set whose signatures the
flood has already brought (ms): the tuples, 5,000 cache probes, a small
device batch for what the cache lost, the `check_valid` pass.

The reading is `txset_validate_ms.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("txset_validate_ms.txset")(cell)
