"""Ledger layer, replayed closes: what the completion worker stood still
inside a ledger's tail, per transaction applied (us): total of the
program's `ledger.close.complete` zone less its on-CPU seconds (the
derived name `ledger.close.complete.onCpu`: not a zone of its own).
`sqlite3_step` and gzip let go of the interpreter and count as on-CPU
while they compute; what is left is the wait for the interpreter, which
the next ledger's apply holds, and sqlite's waits for the file. A
replay's zones are read before its node shuts down, so the checkpoint
ledger's own tail is in none of them (as in `history_tail_us_per_tx.*`).
Nothing where the program reports no on-CPU seconds, or not for every
tail."""


def read(cell):
    count, wall = cell.zones.get("ledger.close.complete", (0, 0.0))
    measured, on_cpu = cell.zones.get("ledger.close.complete.onCpu",
                                      (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not count or measured != count or not txs:
        return None
    return (wall - on_cpu) / txs * 1e6
