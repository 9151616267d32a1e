"""Herder admission: what a `recv_transaction` call stood still (us):
total of the program's `herder.recvTransaction` zone less its on-CPU
seconds (the derived name `herder.recvTransaction.onCpu`: not a zone of
its own), per call. The site reads the thread clock only while a recorder
records, as it does in the traced run, and then twice a close, round the
whole run of a ledger's back-to-back calls. The native verify lets go of
the interpreter and has to take it back from the completion worker:
`herder_admit_us_per_tx.live` is the wall of the same calls. A ledger
whose calls did not come back to back (something else of the thread's
lay between them) has no on-CPU seconds, so the reading is the mean wall
of all calls less the mean on-CPU seconds of those measured; nothing
where fewer than half were."""


def read(cell):
    count, wall = cell.zones.get("herder.recvTransaction", (0, 0.0))
    measured, on_cpu = cell.zones.get("herder.recvTransaction.onCpu",
                                      (0, 0.0))
    if not count or 2 * measured < count:
        return None
    return (wall / count - on_cpu / measured) * 1e6
