"""Crypto layer: share of the validated sets' signatures that the verify
cache answered (%): ~100 in this cell, where the flood the cell itself
plays brought every one of them; it falls once the 65,535-entry cache is
full and has evicted verdicts between flood and set.

The reading is `txset_cached_share.txset`'s, made by that reader, in the cell
`txset-5000-flood.flooded`."""


def read(cell):
    return cell.spec.layer_reader("txset_cached_share.txset")(cell)
