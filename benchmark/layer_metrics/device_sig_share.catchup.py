"""Share of the replayed checkpoints' payment signatures that reached
the device inside the window: sum of the program's
`crypto.verify.dispatch.batch` histogram / payment signatures of the
checkpoints whose replay got as far as its batch (%). A little over 100
when all do: the account-creation transactions ride the batch too."""


def read(cell):
    signatures = cell.traffic_counts.get("signatures_in_checkpoints")
    if not signatures:
        return None
    _, on_device = cell.counters.get("crypto.verify.dispatch.batch",
                                     (0, 0.0))
    return 100.0 * on_device / signatures
