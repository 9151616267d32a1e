"""The ledger-close completion pipeline (deferred post-commit I/O).

Covers the perf_opt tentpole: the consensus-critical close segment
returns before tx-history/meta/publish run; a per-ledger barrier makes
readers (next close, DB snapshot readers, shutdown) join first; a crash
between the seal commit and the completion flush recovers from the last
durable header; and the deferred schedule is byte-identical to the
synchronous one (header hashes + tx meta).

Plus the satellites that ride the same paths: HAS snapshot at queue
time, GC protection for publish-queue/catchup buckets, the passive
index sidecar, and the DNS cache TTL.
"""

import json
import os
import sqlite3
import threading
import time

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.db.database import Database
from stellar_core_tpu.herder import make_tx_set_from_transactions
from stellar_core_tpu.ledger.completion import CloseCompletionQueue
from stellar_core_tpu.ledger.ledger_manager import (LedgerCloseData,
                                                    LedgerManager)
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger import StellarValue

import test_ledger_close as lc
import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment


# ----------------------------------------------------- completion queue --

def test_completion_queue_runs_in_order_and_joins():
    q = CloseCompletionQueue()
    done = []

    def job(n):
        def run():
            time.sleep(0.01)
            done.append(n)
        return run

    for n in (2, 3, 4):
        q.submit(n, job(n))
    q.join()
    assert done == [2, 3, 4]
    assert q.pending() == 0
    assert q.last_completed() == 4


def test_completion_queue_error_surfaces_on_join():
    q = CloseCompletionQueue()

    def boom():
        raise OSError("disk gone")

    q.submit(7, boom)
    with pytest.raises(RuntimeError, match="ledger 7"):
        q.join()
    # the error is STICKY: a reader thread swallowing the first raise
    # cannot hide the failure from the consensus path
    done = []
    q.submit(8, lambda: done.append(8))
    with pytest.raises(RuntimeError, match="ledger 7"):
        q.join()
    assert done == [8]          # later jobs still ran
    q.join(reraise=False)       # shutdown drain ignores it


def test_completion_queue_join_from_worker_is_noop():
    q = CloseCompletionQueue()
    saw = {}

    def introspect():
        # a completion job reading its own artifacts must not deadlock
        q.join()
        saw["ok"] = True

    q.submit(1, introspect)
    q.join()
    assert saw.get("ok")


# ------------------------------------------------------ barrier ordering --

def _close_payment_ledger(lm, db=None):
    """One close via the deferred pipeline (no manual-close join)."""
    mk = lc.master_key()
    seq = lc.master_seq(lm)
    dest = SecretKey.pseudo_random_for_testing(lm.get_last_closed_ledger_num())
    tx = lc.make_tx(lm, mk, seq + 1,
                    [op_create_account(lc.xpk(dest), 10 ** 9)])
    lcl = lm.get_last_closed_ledger_header()
    frame, applicable, _ = make_tx_set_from_transactions(
        [tx], lcl, lc.NETWORK_ID)
    value = StellarValue(txSetHash=frame.get_contents_hash(),
                         closeTime=1000 + lcl.ledgerSeq)
    lm.close_ledger(LedgerCloseData(lcl.ledgerSeq + 1, frame, value))


def test_reader_barrier_orders_tx_history_reads():
    """A direct DB read of txhistory right after close_ledger returns
    must observe the completed rows, even though they are written on the
    background worker — the Database-level barrier joins first."""
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    assert lm.defer_completion

    # make the completion tail visibly slow so an unbarriered read
    # would deterministically miss the rows
    orig = lm._store_tx_history

    def slow_store(*a, **kw):
        time.sleep(0.15)
        orig(*a, **kw)

    lm._store_tx_history = slow_store
    _close_payment_ledger(lm)
    # close_ledger returned while completion sleeps; the read barriers
    row = db.query_one("SELECT txbody FROM txhistory WHERE ledgerseq=2")
    assert row is not None
    assert lm._completion.pending() == 0


def test_next_close_joins_previous_completion():
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    order = []
    orig = lm._store_tx_history

    def slow_store(seq, *a, **kw):
        time.sleep(0.1)
        order.append(("complete", seq))
        orig(seq, *a, **kw)

    lm._store_tx_history = slow_store
    _close_payment_ledger(lm)
    order.append(("close-returned", 2))
    _close_payment_ledger(lm)   # must join ledger 2's completion first
    order.append(("close-returned", 3))
    lm.join_completion()
    assert order.index(("close-returned", 2)) < \
        order.index(("complete", 2)) < order.index(("close-returned", 3)) \
        and order[-1] != ("complete", 2)
    assert order.index(("complete", 2)) < order.index(("complete", 3))


_HISTORY_TABLES = {
    "txhistory": "ledgerseq, txindex, txid, txbody, txresult, txmeta",
    "txfeehistory": "ledgerseq, txindex, txid, txchanges",
    "txsethistory": "ledgerseq, isgeneralized, txset",
}


def _history_rows(db):
    """Every row of the three tables the completion tail writes."""
    return {table: [tuple(bytes(c) if isinstance(c, (bytes, memoryview))
                          else c for c in r)
                    for r in db.query_all(
                        f"SELECT {cols} FROM {table} ORDER BY 1, 2")]
            for table, cols in _HISTORY_TABLES.items()}


def _run_three_closes(db, defer=True):
    """(LCL hash, streamed meta bytes, history rows) of three closes."""
    metas = []
    db.initialize()
    lm = lc.make_manager(db=db)
    lm.defer_completion = defer
    lm.meta_stream = metas.append
    for _ in range(3):
        _close_payment_ledger(lm)
    lm.join_completion()
    rows = _history_rows(db)
    assert [len(r) for r in rows.values()] == [3, 3, 3]
    return (lm.get_last_closed_ledger_hash(),
            [m.to_bytes() for m in metas], rows)


def test_deferred_path_byte_identical_to_synchronous():
    """Golden regression: header hashes, emitted meta AND the history
    tables' rows are byte-identical between the deferred and inline
    completion schedules."""
    deferred = _run_three_closes(Database(":memory:"), True)
    inline = _run_three_closes(Database(":memory:"), False)
    assert deferred[0] == inline[0]
    assert deferred[1] == inline[1]
    assert deferred[2] == inline[2]


# ------------------------------- the next close beside the previous tail --

class _GatedTail:
    """Holds ledger `seq`'s `_store_tx_history` (inside the tail's SQL
    transaction) until `open()`, and keeps the order of what happened."""

    def __init__(self, lm, seq):
        self.order = []
        self.held = threading.Event()       # the tail is inside its BEGIN
        self.applied = threading.Event()    # close seq+1 is past applyTx
        self._gate = threading.Event()
        store, apply, header = (lm._store_tx_history,
                                lm._apply_transactions, lm._store_header)

        def gated_store(s, *a):
            if s == seq:
                self.held.set()
                assert self._gate.wait(10)
            self.order.append(("tail-sql", s))
            store(s, *a)

        def noted_apply(ltx, *a):
            out = apply(ltx, *a)
            self.order.append(("applied", ltx.get_header().ledgerSeq))
            if ltx.get_header().ledgerSeq == seq + 1:
                self.applied.set()
            return out

        def noted_header(h):
            self.order.append(("seal-sql", h.ledgerSeq))
            header(h)

        lm._store_tx_history = gated_store
        lm._apply_transactions = noted_apply
        lm._store_header = noted_header

    def open(self):
        self.order.append(("gate-open",))
        self._gate.set()

    def open_after_apply(self, delay=0.2):
        """Open the gate `delay` seconds after close seq+1 has left
        `applyTx`: room for it to run into `seal`, and a wait at the
        barrier that a loaded machine cannot shrink to nothing."""
        def run():
            assert self.applied.wait(10)
            time.sleep(delay)
            self.open()
        opener = threading.Thread(target=run)
        opener.start()
        return opener


def _file_db(tmp_path):
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    return db


def test_next_close_applies_beside_previous_tail(tmp_path):
    """Ledger 3 runs prepare, fees and applyTx while ledger 2's tail is
    in flight, and does not enter `seal` until that tail is durable."""
    lm = lc.make_manager(db=_file_db(tmp_path))
    gate = _GatedTail(lm, 2)
    # a bare LedgerManager's zones are the process-wide registry's
    waits0 = lm.perf.report().get("ledger.close.completeWait",
                                  {"count": 0, "total_ms": 0.0})

    opener = gate.open_after_apply()
    _close_payment_ledger(lm)
    assert gate.held.wait(10)
    _close_payment_ledger(lm)
    opener.join()
    lm.join_completion()
    at = gate.order.index
    assert at(("applied", 3)) < at(("gate-open",)) < at(("tail-sql", 2)) \
        < at(("seal-sql", 3)) < at(("tail-sql", 3))
    waits = lm.perf.report()["ledger.close.completeWait"]
    assert waits["count"] - waits0["count"] == 2
    assert waits["total_ms"] - waits0["total_ms"] >= 100


def test_closing_thread_reads_beside_open_tail_transaction(tmp_path):
    """A file-backed database gives the tail a connection of its own:
    the closing thread's prefetch and entry loads return while the
    tail's transaction is open, and see no row of it before COMMIT."""
    from stellar_core_tpu.xdr.ledger_entries import LedgerKey
    db = _file_db(tmp_path)
    assert db._tail_conn is not None
    lm = lc.make_manager(db=db)
    gate = _GatedTail(lm, 2)
    _close_payment_ledger(lm)
    assert gate.held.wait(10)
    dest = SecretKey.pseudo_random_for_testing(1)
    key = LedgerKey.account(lc.xpk(dest))
    lm.root._cache.clear()
    t0 = time.monotonic()
    assert lm.root.prefetch([key]) == 1         # through SQL: cache is cold
    assert lm.root._lookup(key.to_bytes()) is not None
    assert db.query_one("SELECT COUNT(*) FROM ledgerheaders")[0] == 2
    assert time.monotonic() - t0 < 5
    assert lm._completion.pending() == 1        # ... and the tail still open
    assert db._conn.execute(
        "SELECT COUNT(*) FROM txsethistory").fetchone()[0] == 0
    gate.open()
    lm.join_completion()
    assert db.query_one("SELECT COUNT(*) FROM txsethistory")[0] == 1


def test_failed_tail_halts_next_close_before_it_commits(tmp_path):
    """A tail that raises stops the next close at the moved barrier:
    the close has applied in memory, and nothing of it is in the
    database."""
    db = _file_db(tmp_path)
    lm = lc.make_manager(db=db)
    store = lm._store_tx_history

    def failing_store(seq, *a):
        if seq == 2:
            raise OSError("disk gone")
        store(seq, *a)

    lm._store_tx_history = failing_store
    _close_payment_ledger(lm)
    applied = []
    apply = lm._apply_transactions
    lm._apply_transactions = lambda *a: applied.append(1) or apply(*a)
    accounts = db.query_one("SELECT COUNT(*) FROM accounts")[0]
    with pytest.raises(RuntimeError, match="ledger 2"):
        _close_payment_ledger(lm)
    assert applied == [1]
    assert lm.get_last_closed_ledger_num() == 2
    assert db.query_one("SELECT MAX(ledgerseq) FROM ledgerheaders")[0] == 2
    assert db.query_one("SELECT COUNT(*) FROM accounts")[0] == accounts
    # the failed tail rolled back whole, and the error stays
    assert db._conn.execute(
        "SELECT COUNT(*) FROM txhistory").fetchone()[0] == 0
    with pytest.raises(RuntimeError, match="ledger 2"):
        lm.join_completion()


def test_memory_database_keeps_one_connection_and_the_same_results(
        tmp_path):
    """`:memory:` has no second connection to open; what the closes
    leave is the same either way."""
    memory = Database(":memory:")
    assert memory._tail_conn is None
    on_file = Database(str(tmp_path / "node.db"))
    assert on_file._tail_conn is not None
    assert _run_three_closes(memory) == _run_three_closes(on_file)
    # one connection: the tail's transaction is the shared session's
    with memory.tail_transaction():
        assert memory._tx_depth == 1
    with on_file.tail_transaction():
        assert on_file._tx_depth == 0
        on_file.execute("INSERT OR REPLACE INTO storestate "
                        "(statename, state) VALUES ('k', 'v')")
        assert on_file._conn.execute(
            "SELECT state FROM storestate WHERE statename='k'"
        ).fetchone() is None
    assert on_file.query_one(
        "SELECT state FROM storestate WHERE statename='k'")[0] == "v"


def test_insert_rows_is_executemany_in_fewer_statements(tmp_path,
                                                        monkeypatch):
    """The tail's bulk inserts: the rows `executemany` leaves (keys that
    repeat inside a batch included), a row a mark of the meter, one
    statement per `PACKED_INSERT_ROWS`."""
    from stellar_core_tpu.db import database as dbmod
    from stellar_core_tpu.util.metrics import MetricsRegistry
    sql = ("INSERT OR REPLACE INTO txfeehistory "
           "(txid, ledgerseq, txindex, txchanges) VALUES (?,?,?,?)")
    rows = [(bytes([i % 251]) * 32, 7, i % 40, b"changes %d" % i)
            for i in range(45)]
    plain = Database(":memory:")
    plain.initialize()
    plain.executemany(sql, rows)
    monkeypatch.setattr(dbmod, "PACKED_INSERT_ROWS", 16)
    metrics = MetricsRegistry()
    packed = Database(str(tmp_path / "node.db"), metrics=metrics)
    packed.initialize()
    statements = []
    packed._tail_conn.set_trace_callback(statements.append)
    meter = metrics.meter("database", "query", "exec")
    marks = meter.count
    with packed.tail_transaction():
        packed.insert_rows(sql, rows)
    assert meter.count - marks == 45
    # the trace shows the statements with their values bound
    assert [s.count("), (") + 1 for s in statements
            if s.startswith("INSERT")] == [16, 16, 13]
    assert _history_rows(packed) == _history_rows(plain)
    assert len(_history_rows(packed)["txfeehistory"]) == 40


def test_tail_transaction_counts_a_busy_file(tmp_path):
    """Another writer on the file when the tail begins: the tail waits
    for it and `database.tail.busy` says so."""
    from stellar_core_tpu.util.metrics import MetricsRegistry
    metrics = MetricsRegistry()
    db = Database(str(tmp_path / "node.db"), metrics=metrics)
    db.initialize()
    busy = metrics.counter("database", "tail", "busy")
    with db.tail_transaction():
        pass
    assert busy.count == 0
    other = sqlite3.connect(db.path, check_same_thread=False)
    other.isolation_level = None
    other.execute("BEGIN IMMEDIATE")
    release = threading.Timer(0.2, other.execute, ("COMMIT",))
    release.start()
    with db.tail_transaction():
        db.execute("INSERT OR REPLACE INTO storestate "
                   "(statename, state) VALUES ('k', 'v')")
    release.join()
    other.close()
    assert busy.count == 1
    assert db.query_one(
        "SELECT state FROM storestate WHERE statename='k'")[0] == "v"
    db.close()


# ------------------------------------------- one encoding for both sinks --

def _count_encodes(monkeypatch):
    from stellar_core_tpu.ledger import ledger_manager as lm_mod
    calls = []
    orig = lm_mod._encode_tx_meta

    def counting(meta, ledger_version=0):
        calls.append(ledger_version)
        return orig(meta, ledger_version)

    monkeypatch.setattr(lm_mod, "_encode_tx_meta", counting)
    return calls


def _close_three_payments(lm):
    mk = lc.master_key()
    seq = lc.master_seq(lm)
    lc.close_with(lm, [
        lc.make_tx(lm, mk, seq + 1 + i, [op_payment(
            lc.MuxedAccount.from_ed25519(mk.public_key().raw), 1 + i)])
        for i in range(3)])
    lm.join_completion()


def test_tx_meta_is_encoded_once_per_transaction(tmp_path, monkeypatch):
    """Both sinks on (history rows, meta stream and debug segment): one
    `_encode_tx_meta` call per transaction per close."""
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    assert lm.stores_history_misc
    lm.meta_stream = [].append
    lm.meta_debug_dir = str(tmp_path / "meta-debug")
    calls = _count_encodes(monkeypatch)
    _close_three_payments(lm)
    assert calls == [21, 21, 21]
    _close_three_payments(lm)
    assert len(calls) == 6
    assert db.query_one("SELECT COUNT(*) FROM txhistory")[0] == 6
    assert db.query_one("SELECT COUNT(*) FROM txfeehistory")[0] == 6


def test_tx_meta_is_not_encoded_without_a_sink(monkeypatch):
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    lm.stores_history_misc = False
    assert lm.meta_stream is None and lm.meta_debug_dir is None
    calls = _count_encodes(monkeypatch)
    _close_three_payments(lm)
    assert calls == []
    assert db.query_one("SELECT COUNT(*) FROM txhistory")[0] == 0
    assert lm.get_last_closed_ledger_num() == 2


@pytest.mark.parametrize("delay_meta", [False, True])
def test_meta_stream_receives_the_same_close_meta(delay_meta):
    """A `meta_stream` consumer keeps receiving a LedgerCloseMeta
    object, equal to the one built with an encoding pass of its own
    (as before the tail shared one), one ledger late under
    `delay_meta`."""
    import test_history_tail as ht
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    lm.delay_meta = delay_meta
    streamed = []
    lm.meta_stream = streamed.append
    tails = ht.capture_tail(lm)
    for _ in range(3):
        _close_three_payments(lm)
    want = [ht.plain_close_meta(t) for t in tails]
    assert len(want) == 3
    if delay_meta:
        assert streamed == want[:2]
        lm.flush_delayed_meta()
    assert streamed == want
    assert [m.to_bytes() for m in streamed] == \
        [ht.plain_bytes(m) for m in want]


# -------------------------------------------------- crash mid-completion --

def _file_cfg(tmp_path):
    cfg = get_test_config()
    cfg.DATABASE = f"sqlite3://{tmp_path}/node.db"
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    return cfg


def test_crash_mid_completion_restart(tmp_path):
    """Kill after seal, before tx-history/meta flush: the node restarts
    from the last durable header (seal committed entries + header + HAS
    atomically) and keeps closing ledgers cleanly."""
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _file_cfg(tmp_path))
    app.start()
    master = m1.master_account(app)
    dest = m1.AppAccount(app, SecretKey.from_seed(b"\x09" * 32))
    m1.submit(app, master.tx([op_create_account(dest.account_id, 10**10)]))
    app.manual_close()
    lcl_before = app.ledger_manager.get_last_closed_ledger_num()

    # simulate the crash: the completion job for the next close is lost
    # (worker killed after the seal transaction committed)
    app.ledger_manager._completion.submit = lambda seq, fn: None
    m1.submit(app, master.tx([op_payment(dest.muxed, 777)]))
    app.manual_close()
    crashed_seq = app.ledger_manager.get_last_closed_ledger_num()
    assert crashed_seq == lcl_before + 1
    expected_hash = app.ledger_manager.get_last_closed_ledger_hash()
    # the seal segment was durable...
    assert app.database.query_one(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
        (crashed_seq,)) is not None
    # ...but the completion tail never flushed
    assert app.database.query_one(
        "SELECT txbody FROM txhistory WHERE ledgerseq=?",
        (crashed_seq,)) is None
    # abandon the app without shutdown (no drain, no clean close)

    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                              _file_cfg(tmp_path))
    app2.start()
    try:
        lm2 = app2.ledger_manager
        # recovered from the last durable header, hashes intact
        assert lm2.get_last_closed_ledger_num() == crashed_seq
        assert lm2.get_last_closed_ledger_hash() == expected_hash
        # the gap was recorded + healed: the marker now matches the LCL
        from stellar_core_tpu.main.persistent_state import StateEntry
        assert int(app2.persistent_state.get(
            StateEntry.LAST_CLOSE_COMPLETED)) == crashed_seq
        # and the node replays forward cleanly, with complete artifacts
        master2 = m1.master_account(app2)
        dest2 = m1.AppAccount(app2, SecretKey.from_seed(b"\x09" * 32))
        dest2.sync_seq()
        m1.submit(app2, master2.tx([op_payment(dest2.muxed, 555)]))
        app2.manual_close()
        new_seq = lm2.get_last_closed_ledger_num()
        assert new_seq == crashed_seq + 1
        assert app2.database.query_one(
            "SELECT txbody FROM txhistory WHERE ledgerseq=?",
            (new_seq,)) is not None
    finally:
        app2.shutdown()


def _tail_counts(app):
    j = app.metrics.to_json()
    return tuple(j[n]["count"] for n in (
        "ledger.close.tail.hidden", "ledger.close.tail.waited",
        "database.tail.busy"))


def test_manual_closes_run_their_tails_beside_the_next_submission(tmp_path):
    """`manual_close` returns at the commit (until PR 34 it joined its
    own tail, so the barrier before `seal` found an empty queue at every
    close). Twenty closes with submissions between them: no close opens
    `herder.joinCompletion`, the barrier counts each close once, the
    tail's transaction never finds the file locked (the close writes
    LAST_CLOSED_LEDGER inside its own transaction, not after it), and a
    reader of the tables finds every row. Closes that follow each other
    directly make the barrier wait, and say so."""
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            _file_cfg(tmp_path)) as app:
        app.start()
        master = m1.master_account(app)
        for _ in range(20):
            for _ in range(3):
                m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            app.manual_close()
        hidden, waited, busy = _tail_counts(app)
        assert hidden + waited == 20 and busy == 0
        assert "herder.joinCompletion" not in app.perf.report()
        assert app.database.query_one(
            "SELECT COUNT(*), COUNT(DISTINCT ledgerseq) FROM txhistory") \
            == (60, 20)
        lm = app.ledger_manager
        gate = _GatedTail(lm, lm.get_last_closed_ledger_num() + 1)
        first = lm.get_last_closed_ledger_num() + 1
        for seq in (first, first + 1):
            if seq > first:
                assert gate.held.wait(10)
                opener = gate.open_after_apply()
            lcl = lm.get_last_closed_ledger_header()
            frame, _, _ = make_tx_set_from_transactions(
                [], lcl, app.config.network_id())
            lm.close_ledger(LedgerCloseData(seq, frame, StellarValue(
                txSetHash=frame.get_contents_hash(),
                closeTime=lcl.scpValue.closeTime + 1)))
        opener.join()
        lm.join_completion()
        assert _tail_counts(app) == (hidden + 1, waited + 1, 0)
        zone = app.perf.report()["ledger.close.completeWait"]
        assert zone["count"] == 22 and zone["total_ms"] >= 100


# ----------------- a manual close returns at the commit; readers join --

class _HeldWorker:
    """Holds the completion worker at the head of every tail, before
    any of it has run, until `release()` (or `release_after`)."""

    def __init__(self, lm):
        self._gate = threading.Event()
        complete = lm._complete_close

        def held(*a, **kw):
            assert self._gate.wait(10)
            complete(*a, **kw)
        lm._complete_close = held

    def release(self):
        self._gate.set()

    def release_after(self, seconds=0.15):
        """From another thread, while the caller is inside a reader's
        join: the reader can return only through the join."""
        threading.Timer(seconds, self._gate.set).start()


def _standalone(tmp_path, **settings):
    cfg = _file_cfg(tmp_path)
    for key, value in settings.items():
        setattr(cfg, key, value)
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _submit_payment(app):
    master = m1.master_account(app)
    frame = master.tx([op_payment(master.muxed, 1)])
    assert m1.submit(app, frame)["status"] == "PENDING"
    return frame


@pytest.mark.parametrize("how", ["in-process", "route"])
def test_manual_close_returns_at_the_commit_with_its_tail_pending(
        tmp_path, how):
    """Header, entries, bucket list, local HAS and LAST_CLOSED_LEDGER
    are committed when the call (or the admin route, which does the
    same) returns; of the tail nothing has run, and nothing joined it."""
    from stellar_core_tpu.main.persistent_state import StateEntry
    app = _standalone(tmp_path)
    try:
        lm, db = app.ledger_manager, app.database
        held = _HeldWorker(lm)
        frame = _submit_payment(app)
        if how == "route":
            out = app.command_handler.handle("manualclose")
            assert "sequence number 2" in out["status"], out
        else:
            app.manual_close()
        assert lm.get_last_closed_ledger_num() == 2
        assert lm.completion_pending()
        assert "herder.joinCompletion" not in app.perf.report()
        # past the facade's barrier, on the raw connection
        raw = db._conn.execute
        assert raw("SELECT COUNT(*) FROM ledgerheaders "
                   "WHERE ledgerseq=2").fetchone()[0] == 1
        assert raw("SELECT state FROM storestate WHERE statename=?",
                   (StateEntry.LAST_CLOSED_LEDGER.value,)).fetchone()[0] \
            == lm.get_last_closed_ledger_hash().hex()
        assert raw("SELECT state FROM storestate WHERE statename=?",
                   (StateEntry.LAST_CLOSE_COMPLETED.value,)
                   ).fetchone()[0] == "1"
        assert raw("SELECT COUNT(*) FROM txhistory").fetchone()[0] == 0
        assert app.tx_status.lookup(frame.full_hash()) is None
        # the committed state serves the next admission beside the tail
        _submit_payment(app)
        held.release()
        app.herder.join_completion()
        assert not lm.completion_pending()
        assert app.perf.report()["herder.joinCompletion"]["count"] == 1
        assert raw("SELECT COUNT(*) FROM txhistory").fetchone()[0] == 1
    finally:
        app.shutdown()


def _read_table(table):
    def read(app, frame, seq):
        return app.database.query_one(
            f"SELECT COUNT(*) FROM {table} WHERE ledgerseq=?", (seq,))[0]
    return read


def _read_marker(app, frame, seq):
    from stellar_core_tpu.main.persistent_state import StateEntry
    return int(app.persistent_state.get(
        StateEntry.LAST_CLOSE_COMPLETED)) == seq


def _read_meta_stream(app, frame, seq):
    app.herder.join_completion()        # the stream's consumer joins
    return [m.value.ledgerHeader.header.ledgerSeq
            for m in app.test_metas] == [seq]


def _read_debug_segment(app, frame, seq):
    from test_debug_meta_segment import SEG63, records_of
    app.herder.join_completion()        # who lists the segments joins
    return len(records_of(
        os.path.join(app.ledger_manager.meta_debug_dir, SEG63)))


def _read_tx_status(app, frame, seq):
    out = app.command_handler.handle(
        "txstatus", {"hash": frame.full_hash().hex(),
                     "deadline_ms": "5000"})
    return out["found"] and out["ledger_seq"] == seq


def _read_snapshot_info(app, frame, seq):
    return app.command_handler.handle("snapshotinfo")["tx_status_entries"]


def _read_maintenance(app, frame, seq):
    # deletes history below the LCL's floor: through the tables' barrier
    app.maintainer.perform_maintenance(10)
    return app.database._conn.execute(
        "SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?",
        (seq,)).fetchone()[0]


@pytest.mark.parametrize("reader", [
    pytest.param(_read_table("txhistory"), id="txhistory"),
    pytest.param(_read_table("txsethistory"), id="txsethistory"),
    pytest.param(_read_table("txfeehistory"), id="txfeehistory"),
    pytest.param(_read_marker, id="marker"),
    pytest.param(_read_meta_stream, id="meta-stream"),
    pytest.param(_read_debug_segment, id="debug-segment"),
    pytest.param(_read_tx_status, id="txstatus-route"),
    pytest.param(_read_snapshot_info, id="snapshotinfo-route"),
    pytest.param(_read_maintenance, id="maintenance"),
])
def test_reader_of_a_pending_tail_sees_it_finished(tmp_path, reader):
    """Each reader of what the tail writes, asked while the worker is
    held before ledger 2's tail: it returns only once the tail has run,
    and sees what a synchronous close would have shown it."""
    app = _standalone(tmp_path, METADATA_DEBUG_LEDGERS=64)
    try:
        lm = app.ledger_manager
        app.test_metas = []
        lm.meta_stream = app.test_metas.append
        held = _HeldWorker(lm)
        frame = _submit_payment(app)
        app.manual_close()
        assert lm.completion_pending()
        held.release_after()
        t0 = time.monotonic()
        assert reader(app, frame, 2) == 1
        assert time.monotonic() - t0 >= 0.1     # it stood at the join
        assert not lm.completion_pending()
    finally:
        app.shutdown()


def test_reader_of_a_pending_publish_sees_the_checkpoint(tmp_path):
    """A checkpoint's publish rides ledger 63's tail: `manual_close`
    returns before it, and who asks the history manager what has been
    published (or what is still queued) joins first."""
    cfg, root = _archive_cfg(tmp_path)
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        lm, hm = app.ledger_manager, app.history_manager
        while lm.get_last_closed_ledger_num() < 62:
            app.manual_close()
        app.herder.join_completion()
        held = _HeldWorker(lm)
        app.manual_close()
        assert lm.get_last_closed_ledger_num() == 63
        assert lm.completion_pending() and hm._published == 0
        assert not os.path.exists(
            os.path.join(root, ".well-known/stellar-history.json"))
        held.release_after()
        assert hm.published_count == 1
        assert hm.publish_queue_length() == 0
        with open(os.path.join(
                root, ".well-known/stellar-history.json")) as f:
            assert json.load(f)["currentLedger"] == 63


@pytest.mark.parametrize("how", ["in-process", "route"])
def test_failed_tail_raises_at_the_next_manual_close(tmp_path, how):
    """The close whose tail fails has returned; the failure is sticky
    and halts the next `manual_close` at its barrier with nothing of
    that ledger written, as it halts an SCP-driven close."""
    app = _standalone(tmp_path)
    try:
        lm, db = app.ledger_manager, app.database
        store = lm._store_tx_history

        def failing_store(seq, *a):
            if seq == 2:
                raise OSError("disk gone")
            store(seq, *a)
        lm._store_tx_history = failing_store
        _submit_payment(app)
        app.manual_close()                  # ledger 2: returns
        assert lm.get_last_closed_ledger_num() == 2
        _submit_payment(app)
        accounts = db.query_one("SELECT COUNT(*) FROM accounts")[0]
        if how == "route":
            out = app.command_handler.handle("manualclose")
            assert "ledger 2" in out["exception"], out
        else:
            with pytest.raises(RuntimeError, match="ledger 2"):
                app.manual_close()
        assert lm.get_last_closed_ledger_num() == 2
        assert db.query_one(
            "SELECT MAX(ledgerseq) FROM ledgerheaders")[0] == 2
        assert db.query_one("SELECT COUNT(*) FROM accounts")[0] == accounts
        assert db._conn.execute(
            "SELECT COUNT(*) FROM txhistory").fetchone()[0] == 0
        with pytest.raises(RuntimeError, match="ledger 2"):
            app.herder.join_completion()    # and every reader's join
    finally:
        app.shutdown()


# ------------------------------------------------ HAS snapshot at queue --

def _archive_cfg(tmp_path, delay=0.0):
    archive_root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.PUBLISH_TO_ARCHIVE_DELAY = delay
    cfg.HISTORY = {"test": {
        "get": f"cp {archive_root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
               f"cp {{0}} {archive_root}/{{1}}",
    }}
    return cfg, archive_root


def test_publish_records_queue_time_has(tmp_path):
    """With PUBLISH_TO_ARCHIVE_DELAY, ledgers keep closing between
    queue and publish; the published stellar-history.json must record
    checkpoint 63's OWN bucket levels, not a later ledger's."""
    cfg, root = _archive_cfg(tmp_path, delay=30.0)
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        while app.ledger_manager.get_last_closed_ledger_num() < 63:
            # churn state every close so the live bucket list keeps
            # changing during the publish delay
            m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            app.manual_close()
        queued = app.history_manager._publish_queue
        assert len(queued) == 1 and queued[0].seq == 63
        snapshot_json = queued[0].has.to_json()
        # keep closing during the delay — the live list moves on
        for _ in range(8):
            m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            app.manual_close()
        from stellar_core_tpu.history.archive import HistoryArchiveState
        live_now = HistoryArchiveState.from_bucket_list(
            app.ledger_manager.get_last_closed_ledger_num(),
            app.bucket_manager.bucket_list,
            app.config.NETWORK_PASSPHRASE)
        assert json.loads(live_now.to_json())["currentBuckets"] != \
            json.loads(snapshot_json)["currentBuckets"]
        app.clock.crank_for(35.0)
        assert app.history_manager.published_count == 1
        with open(os.path.join(
                root, ".well-known/stellar-history.json")) as f:
            published = json.load(f)
        assert published == json.loads(snapshot_json)
        assert published["currentLedger"] == 63


def test_gc_keeps_buckets_of_queued_checkpoint(tmp_path):
    """forget_unreferenced_buckets must not unlink bucket files a
    queued-but-unpublished checkpoint still references."""
    cfg, root = _archive_cfg(tmp_path, delay=30.0)
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        while app.ledger_manager.get_last_closed_ledger_num() < 63:
            m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            app.manual_close()
        queued_hashes = app.history_manager.queued_bucket_hashes()
        assert queued_hashes
        for _ in range(8):
            m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            app.manual_close()
        app.bucket_manager.forget_unreferenced_buckets()
        for h in queued_hashes:
            assert os.path.exists(os.path.join(
                str(tmp_path / "buckets"), f"bucket-{h.hex()}.xdr")), \
                "GC dropped a bucket the publish queue references"
        # the delayed publish then succeeds from the retained files
        app.clock.crank_for(35.0)
        assert app.history_manager.published_count == 1


def test_gc_keeps_pinned_hot_buckets(tmp_path):
    """Hot-archive files adopted by an in-flight catchup are pinned
    until the catchup installs (or abandons) its levels."""
    from stellar_core_tpu.bucket.manager import BucketManager
    bm = BucketManager(str(tmp_path / "b"))
    raw = b"\x00" * 64
    bm.adopt_hot_bucket_raw(raw)
    import hashlib
    path = os.path.join(str(tmp_path / "b"),
                        f"hot-{hashlib.sha256(raw).hexdigest()}.xdr")
    assert os.path.exists(path)
    bm.forget_unreferenced_buckets()
    assert os.path.exists(path), "GC dropped an in-flight catchup bucket"
    bm.clear_hot_pins()
    bm.forget_unreferenced_buckets()
    assert not os.path.exists(path)
    bm.shutdown()


# ------------------------------------------------- passive index sidecar --

def test_index_sidecar_passive_roundtrip(tmp_path):
    from stellar_core_tpu.bucket import bucket_index
    from stellar_core_tpu.bucket.bucket import Bucket
    from stellar_core_tpu.tx.tx_utils import make_account_ledger_entry
    from stellar_core_tpu.xdr.ledger_entries import ledger_entry_key
    from stellar_core_tpu.xdr.types import PublicKey

    entries = []
    for i in range(20):
        le = make_account_ledger_entry(
            PublicKey.ed25519(bytes([i]) * 32), 10**7, seq_num=1)
        entries.append(le)
    b = Bucket.fresh(11, entries, [], [])
    path = str(tmp_path / "bucket-test.xdr")
    b.write_to(path)

    bucket_index.set_persist_index(True)
    try:
        b1 = Bucket.from_file(path)
        k0 = ledger_entry_key(entries[0])
        assert b1.get(k0) is not None
        sidecar = path + ".idx"
        assert os.path.exists(sidecar)
        with open(sidecar, "rb") as f:
            raw = f.read()
        # passive struct format, not a pickle
        assert raw.startswith(bucket_index.SIDECAR_MAGIC)
        assert not raw.startswith(b"\x80")      # pickle protocol marker

        # reload goes through the sidecar and serves identical lookups
        b2 = Bucket.from_file(path)
        idx = b2._build_index()
        for le in entries:
            assert idx.lookup(b2.raw_bytes(),
                              ledger_entry_key(le)) is not None
        assert b2.get(k0).value.to_bytes() == b1.get(k0).value.to_bytes()

        # damaged sidecars are rebuilt, not trusted and not fatal
        with open(sidecar, "wb") as f:
            f.write(b"\x80\x04garbage-that-is-not-an-index")
        b3 = Bucket.from_file(path)
        assert b3.get(k0) is not None
        with open(sidecar, "rb") as f:
            assert f.read().startswith(bucket_index.SIDECAR_MAGIC)

        # stale-tuning sidecars are ignored (None), then rewritten
        bucket_index.configure_index(cutoff_mb=1, page_size_exponent=10)
        b4 = Bucket.from_file(path)
        assert b4.get(k0) is not None
    finally:
        bucket_index.set_persist_index(False)
        bucket_index.configure_index(cutoff_mb=20, page_size_exponent=14)


def test_bucket_module_has_no_pickle():
    import inspect

    from stellar_core_tpu.bucket import bucket
    src = inspect.getsource(bucket)
    assert "pickle" not in src


# ------------------------------------------------------- DNS cache TTL --

def test_dns_cache_ttl_and_no_failure_caching(monkeypatch):
    from stellar_core_tpu.overlay.manager import OverlayManager

    om = object.__new__(OverlayManager)
    om._dns_cache = {}
    calls = {"n": 0}
    results = {"peer.example": OSError("no resolver")}

    import socket

    def fake_resolve(host):
        calls["n"] += 1
        r = results[host]
        if isinstance(r, Exception):
            raise r
        return r

    monkeypatch.setattr(socket, "gethostbyname", fake_resolve)
    # failures are NOT cached: each call retries
    assert om._resolve_host("peer.example") is None
    assert om._resolve_host("peer.example") is None
    assert calls["n"] == 2
    # success IS cached...
    results["peer.example"] = "10.0.0.7"
    assert om._resolve_host("peer.example") == "10.0.0.7"
    assert om._resolve_host("peer.example") == "10.0.0.7"
    assert calls["n"] == 3
    # ...until the TTL expires, after which a record change is seen
    host_ip, expiry = om._dns_cache["peer.example"]
    om._dns_cache["peer.example"] = (host_ip, time.monotonic() - 1)
    results["peer.example"] = "10.0.0.8"
    assert om._resolve_host("peer.example") == "10.0.0.8"
    assert calls["n"] == 4
    # localhost still short-circuits without a resolver
    assert om._resolve_host("localhost") == "127.0.0.1"
    assert calls["n"] == 4


# ----------------------------------------------------- phase instrumentation --

def test_close_emits_phase_zones():
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    _close_payment_ledger(lm)
    lm.join_completion()
    report = lm.perf.report()
    for zone in ("ledger.closeLedger", "ledger.close.completeWait",
                 "ledger.close.prepare", "ledger.close.fees",
                 "ledger.close.applyTx", "ledger.close.seal",
                 "ledger.close.complete", "ledger.close.txHistory",
                 "ledger.close.meta"):
        assert zone in report, f"missing phase zone {zone}"


def test_slow_log_names_guilty_phase():
    from stellar_core_tpu.ledger.ledger_manager import _phase_summary
    s = _phase_summary({"ledger.close.applyTx": 2.1,
                        "ledger.close.seal": 0.3})
    assert s.startswith("applyTx=2100ms")
    assert "seal=300ms" in s
