"""Herder admission: the benchmark's span round its
`herder.recv_transaction` calls, per transaction submitted (us)."""


def read(cell):
    txs = sum(a.get("txs", 0) for _, _, a in cell.spans.named("bench.submit"))
    if not txs:
        return None
    return cell.spans.total("bench.submit") / txs * 1e6
