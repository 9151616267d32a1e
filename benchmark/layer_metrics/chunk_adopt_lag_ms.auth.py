"""What a landed chunk waits for apply to look (ms).

The reading is `chunk_adopt_lag_ms.dense`'s, made by that reader, in the cell
`soroban-auth.auth-replay`."""


def read(cell):
    return cell.spec.layer_reader("chunk_adopt_lag_ms.dense")(cell)
