"""What a landed chunk waits for apply to look (ms), mean over a
replay's four: the second checkpoint's two wait for the first
checkpoint's ledgers to end (`batch_lead_ms.range` is that wait from
the last of them).

The reading is `chunk_adopt_lag_ms.dense`'s, made by that reader, in the cell
`multisig-range.range-replay`."""


def read(cell):
    return cell.spec.layer_reader("chunk_adopt_lag_ms.dense")(cell)
