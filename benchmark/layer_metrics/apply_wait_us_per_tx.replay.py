"""Ledger layer, replayed closes: what the closing thread stood still
inside apply, per transaction applied (us): total of the program's
`ledger.close.applyTx` zone less the zone's on-CPU seconds, which the
program reports under the derived name `ledger.close.applyTx.onCpu`
(not a zone of its own: same hits, the thread clock's seconds). Wall less
on-CPU is the wait for the interpreter, for files and for the staged
apply's pool together; C code that has let go of the interpreter counts
as on-CPU. `apply_us_per_tx.*` is the wall of the same zone. Nothing where
the program reports no on-CPU seconds, or not for every hit of the zone."""


def read(cell):
    count, wall = cell.zones.get("ledger.close.applyTx", (0, 0.0))
    measured, on_cpu = cell.zones.get("ledger.close.applyTx.onCpu",
                                      (0, 0.0))
    txs = cell.traffic_counts.get("transactions")
    if not count or measured != count or not txs:
        return None
    return (wall - on_cpu) / txs * 1e6
