"""Database facade over sqlite3.

Reference shape: src/database/Database.{h,cpp} — a soci session wrapper
with a prepared-statement cache, schema version table and stepwise
`applySchemaUpgrade` (Database.cpp:208-265), plus table layout documented
in docs/db-schema.md (XDR stored as base64/hex TEXT columns; here raw
BLOBs — sqlite handles them natively and there is no wire-compat
requirement on the DB file).

Tables created at `initialize()`:
  storestate      — PersistentState key/value (main/PersistentState.h)
  ledgerheaders   — one row per closed ledger (header XDR + hash)
  txhistory/txfeehistory — applied transactions + fee changes per ledger,
                    keyed (ledgerseq, txindex) and indexed by nothing
                    else: no reader goes by txid (schema v4)
  scphistory/scpquorums  — externalized SCP messages / quorum sets
  accounts/trustlines/offers/accountdata/claimablebalance/liquiditypool
                  — one table per classic ledger-entry type, keyed by the
                    XDR-serialized LedgerKey, entry stored as LedgerEntry
                    XDR BLOB (written by LedgerTxnRoot on commit)
  peers           — overlay peer records (PeerManager)
  ban             — banned node ids (BanManager)
  pubsub          — ExternalQueue cursors
  quoruminfo      — survey/quorum tracking
"""

from __future__ import annotations

import os
import sqlite3
import threading
from contextlib import contextmanager
from typing import Any, Iterable, Optional

from ..util import chaos
from ..util.logging import get_logger
from ..util.metrics import MetricsRegistry

log = get_logger("Database")

# reference: MIN_SCHEMA_VERSION..SCHEMA_VERSION stepwise upgrades
# (Database.cpp:65-66, 208-265). Every version in
# [MIN_SCHEMA_VERSION, SCHEMA_VERSION] has a stepwise
# _apply_schema_upgrade so on-disk state survives software upgrades.
MIN_SCHEMA_VERSION = 1
SCHEMA_VERSION = 4

# v2: scphistory's by-ledger index (history/manager.py reads a
# checkpoint's envelopes by ledgerseq). Until v4 this step also made
# two transaction-hash indexes; it no longer does, so a v1 database
# never builds them over its whole history for v4 to drop.
SCHEMA_V2_STATEMENTS = (
    "CREATE INDEX IF NOT EXISTS scpenvsbyseq ON scphistory (ledgerseq)",
)

# v3: durable publish queue (reference: the publishqueue table,
# HistoryManagerImpl::takeSnapshotAndQueue) — a checkpoint queued but
# not yet published survives a crash, carrying its queue-time HAS
SCHEMA_V3_STATEMENTS = (
    "CREATE TABLE IF NOT EXISTS publishqueue ("
    "ledgerseq INTEGER PRIMARY KEY, has TEXT)",
)

# v4: histbytxid and feehistbytxid go. Nothing in the tree reads
# txhistory or txfeehistory by txid: history/manager.py and the
# maintainer go by ledgerseq, the tables' own (ledgerseq, txindex) key
# (upstream keys them the same way and has no txid index either). Each
# was a b-tree keyed by a uniformly random 32-byte hash: past sqlite's
# page cache every row of a close's completion tail was a random page
# read and a random page written to the WAL, so the tail's transaction
# grew with the history behind it (PERF.md §6, PR 40). A by-txid reader
# that appears brings its index with it, and says what it costs the
# tail. The txid columns stay and are written.
SCHEMA_V4_STATEMENTS = (
    "DROP INDEX IF EXISTS histbytxid",
    "DROP INDEX IF EXISTS feehistbytxid",
)

# version -> the statements of the step that reaches it; each idempotent
_SCHEMA_STEPS = {2: SCHEMA_V2_STATEMENTS, 3: SCHEMA_V3_STATEMENTS,
                 4: SCHEMA_V4_STATEMENTS}

_ENTRY_TABLES = ("accounts", "trustlines", "offers", "accountdata",
                 "claimablebalance", "liquiditypool", "contractdata",
                 "contractcode", "configsettings", "ttl")


def schema_statements() -> list:
    """The full DDL, in sqlite dialect (the canonical form; the
    postgres backend mechanically translates types — reference
    analogue: Database::initialize + each manager's dropAll)."""
    stmts = [
        "CREATE TABLE IF NOT EXISTS storestate ("
        "statename TEXT PRIMARY KEY, state TEXT)",
        "CREATE TABLE IF NOT EXISTS ledgerheaders ("
        "ledgerhash BLOB PRIMARY KEY, prevhash BLOB, "
        "ledgerseq INTEGER UNIQUE, closetime INTEGER, data BLOB)",
        "CREATE TABLE IF NOT EXISTS txhistory ("
        "txid BLOB, ledgerseq INTEGER, txindex INTEGER, "
        "txbody BLOB, txresult BLOB, txmeta BLOB, "
        "PRIMARY KEY (ledgerseq, txindex))",
        "CREATE TABLE IF NOT EXISTS txfeehistory ("
        "txid BLOB, ledgerseq INTEGER, txindex INTEGER, "
        "txchanges BLOB, PRIMARY KEY (ledgerseq, txindex))",
        "CREATE TABLE IF NOT EXISTS txsethistory ("
        "ledgerseq INTEGER PRIMARY KEY, isgeneralized INTEGER, "
        "txset BLOB)",
        "CREATE TABLE IF NOT EXISTS scphistory ("
        "nodeid BLOB, ledgerseq INTEGER, envelope BLOB)",
        "CREATE TABLE IF NOT EXISTS scpquorums ("
        "qsethash BLOB PRIMARY KEY, lastledgerseq INTEGER, qset BLOB)",
    ]
    for t in _ENTRY_TABLES:
        if t == "offers":
            continue
        stmts.append(f"CREATE TABLE IF NOT EXISTS {t} ("
                     "key BLOB PRIMARY KEY, entry BLOB, "
                     "lastmodified INTEGER)")
    stmts += [
        # offers carry order-book columns so best-offer queries run in
        # SQL (reference: LedgerTxnOfferSQL.cpp loadBestOffers)
        "CREATE TABLE IF NOT EXISTS offers ("
        "key BLOB PRIMARY KEY, entry BLOB, lastmodified INTEGER, "
        "sellerid BLOB, offerid INTEGER UNIQUE, "
        "sellingasset BLOB, buyingasset BLOB, "
        "pricen INTEGER, priced INTEGER, price REAL)",
        "CREATE INDEX IF NOT EXISTS bestofferindex ON offers "
        "(sellingasset, buyingasset, price, offerid)",
        "CREATE INDEX IF NOT EXISTS offersbyseller ON offers "
        "(sellerid)",
        "CREATE TABLE IF NOT EXISTS peers ("
        "ip TEXT, port INTEGER, nextattempt INTEGER, "
        "numfailures INTEGER, type INTEGER, PRIMARY KEY (ip, port))",
        "CREATE TABLE IF NOT EXISTS ban (nodeid BLOB PRIMARY KEY)",
        "CREATE TABLE IF NOT EXISTS pubsub ("
        "resid TEXT PRIMARY KEY, lastread INTEGER)",
        "CREATE TABLE IF NOT EXISTS quoruminfo ("
        "nodeid BLOB PRIMARY KEY, qsethash BLOB)",
    ]
    stmts.extend(SCHEMA_V2_STATEMENTS)   # fresh DBs start at the
    stmts.extend(SCHEMA_V3_STATEMENTS)   # current schema version
    # no-ops on a fresh file; `new-db` over a v3 file keeps its tables
    # (IF NOT EXISTS) and must not keep their txid indexes under v4
    stmts.extend(SCHEMA_V4_STATEMENTS)
    return stmts


# secondary UNIQUE constraints: sqlite's OR REPLACE silently deletes
# rows conflicting on ANY unique index; the postgres translation must
# pre-delete on these before its single-target ON CONFLICT upsert
TABLE_SECONDARY_UNIQUES = {
    "ledgerheaders": ("ledgerseq",),
    "offers": ("offerid",),
}

# conflict targets for INSERT OR REPLACE translation (postgres upserts
# need the explicit unique column set)
TABLE_CONFLICT_KEYS = {
    "storestate": ("statename",),
    "ledgerheaders": ("ledgerhash",),
    "txhistory": ("ledgerseq", "txindex"),
    "txfeehistory": ("ledgerseq", "txindex"),
    "txsethistory": ("ledgerseq",),
    "scpquorums": ("qsethash",),
    "peers": ("ip", "port"),
    "ban": ("nodeid",),
    "pubsub": ("resid",),
    "quoruminfo": ("nodeid",),
    "publishqueue": ("ledgerseq",),
    **{t: ("key",) for t in _ENTRY_TABLES},
}


def create_database(config, metrics=None):
    """Backend factory keyed on the DATABASE config URI (reference:
    Database.cpp's soci backend selection, Database.h:87-195)."""
    uri = config.DATABASE
    if uri.startswith("sqlite3://"):
        return Database(uri[len("sqlite3://"):], metrics=metrics)
    if uri.startswith("postgresql://"):
        from .postgres import PostgresDatabase
        return PostgresDatabase(uri, metrics=metrics)
    raise ValueError(f"unsupported DATABASE: {uri}")


# tables written by the deferred ledger-close completion segment; any
# statement touching them first joins the completion queue so readers
# never observe a ledger whose history rows are still in flight
_CLOSE_COMPLETION_TABLES = ("txhistory", "txsethistory", "txfeehistory")


class SchemaMixin:
    """Backend-independent schema machinery shared by the sqlite and
    postgres backends (reference: Database::applySchemaUpgrade is
    backend-neutral over the soci session the same way)."""

    # exception types meaning "table does not exist yet"
    _missing_table_errors: tuple = ()

    # barrier callbacks joined before completion-owned-table statements
    _close_barriers: list = None
    _tx_owner = None

    def add_close_barrier(self, fn) -> None:
        """Register a ledger-close completion barrier (LedgerManager
        wires its completion queue's `reader_barrier` here)."""
        if self._close_barriers is None:
            self._close_barriers = []
        self._close_barriers.append(fn)

    def _completion_barrier(self, sql: str) -> None:
        if self._close_barriers and \
                any(t in sql for t in _CLOSE_COMPLETION_TABLES):
            self.join_close_barriers()

    def join_close_barriers(self) -> None:
        """Join the close-completion tail as a reader of what it writes:
        before any statement on its tables, and before a read of its
        LAST_CLOSE_COMPLETED marker (PersistentState.get)."""
        # a thread already inside its own transaction must not block on
        # the worker (which may need this connection's lock): callers
        # that read completion tables transactionally join beforehand
        if self._tx_owner is threading.current_thread():
            return
        for fn in self._close_barriers or ():
            fn()

    def tail_transaction(self):
        """The scope of the close-completion tail's one transaction. A
        backend with one connection runs it as any other."""
        return self.transaction()

    def writing_transaction(self):
        """The scope of a transaction that writes from its first
        statement. A backend whose writers never invalidate a reader's
        snapshot runs it as any other."""
        return self.transaction()

    def insert_rows(self, sql: str, rows: Iterable[Iterable[Any]]) -> None:
        """`executemany` of a one-row `INSERT ... VALUES (?,...)`, in
        whatever shape the backend runs a bulk insert best."""
        self.executemany(sql, rows)

    def query_one(self, sql: str, params: Iterable[Any] = ()):
        return self.execute(sql, params).fetchone()

    def query_all(self, sql: str, params: Iterable[Any] = ()):
        return self.execute(sql, params).fetchall()

    def initialize(self) -> None:
        """Create all tables from scratch (reference: `new-db`,
        Database::initialize + each manager's dropAll)."""
        with self.writing_transaction():
            for stmt in schema_statements():
                self.execute(stmt)
            self.put_schema_version(SCHEMA_VERSION)
        log.info("database initialized (schema v%d) at %s",
                 SCHEMA_VERSION, self.path)

    def get_schema_version(self) -> int:
        try:
            row = self.query_one(
                "SELECT state FROM storestate WHERE statename='dbschema'")
            return int(row[0]) if row else 0
        except self._missing_table_errors:
            return 0

    def put_schema_version(self, v: int) -> None:
        self.execute(
            "INSERT OR REPLACE INTO storestate (statename, state) "
            "VALUES ('dbschema', ?)", (str(v),))

    def upgrade_to_current_schema(self) -> None:
        """Stepwise schema upgrade (reference: Database.cpp:208-240).
        v0 (no schema at all) takes the full initialize() path; every
        later step is a pure delta so the ladder composes."""
        v = self.get_schema_version()
        if v > SCHEMA_VERSION:
            raise RuntimeError(
                f"DB schema v{v} is newer than supported v{SCHEMA_VERSION}")
        if v == 0:
            self.initialize()
            return
        if v < MIN_SCHEMA_VERSION:
            raise RuntimeError(
                f"DB schema v{v} is older than the minimum supported "
                f"v{MIN_SCHEMA_VERSION}; re-create with new-db")
        while v < SCHEMA_VERSION:
            v += 1
            self._apply_schema_upgrade(v)
            self.put_schema_version(v)

    def _apply_schema_upgrade(self, v: int) -> None:
        """One pure-delta version step (reference:
        Database::applySchemaUpgrade, Database.cpp:208-265)."""
        log.info("applying schema upgrade to v%d", v)
        stmts = _SCHEMA_STEPS.get(v)
        if stmts is None:
            raise RuntimeError(f"unknown schema version {v}")
        with self.transaction():
            for stmt in stmts:
                self.execute(stmt)

    def entry_tables(self) -> tuple:
        return _ENTRY_TABLES


# what either connection of a file-backed database waits for the file's
# other writer before it gives up (ms). The tail should never find one
# (Database.tail_transaction); a writer of the first connection may
# find the tail, where it used to wait for the session lock
BUSY_TIMEOUT_MS = 30000

# rows of one multi-row VALUES statement (Database.insert_rows)
PACKED_INSERT_ROWS = 1000


class Database(SchemaMixin):
    """The sqlite backend: one connection for everything the node does,
    and for a file-backed database a second one that only the ledger
    close's completion tail writes through (`tail_transaction`).

    check_same_thread=False with an explicit lock per connection: the
    node is single-main-threaded by design (docs/architecture.md:24-36),
    but background work (bucket apply, tests) may touch the DB under
    the session lock.
    """

    _missing_table_errors = (sqlite3.OperationalError,)

    def __init__(self, path: str = ":memory:",
                 metrics: Optional[MetricsRegistry] = None):
        self.path = path
        if path != ":memory:":
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._conn = self._connect(path, timeout=BUSY_TIMEOUT_MS / 1000)
        self._variable_limit = self._conn.getlimit(
            sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER)
        self._lock = threading.RLock()
        self._tx_depth = 0
        self._metrics = metrics
        self._query_meter = (metrics.meter("database", "query", "exec")
                            if metrics else None)
        # the tail's connection: WAL lets the closing thread read
        # through `_conn` beside the tail's write. A `:memory:` database
        # is private to its connection, so it keeps the one
        self._tail_conn = None
        self._tail_lock = threading.Lock()
        self._tail_owner = None         # the thread inside the tail's scope
        self._tail_statements = 0       # of the open scope, for the meter
        self._tail_busy = (metrics.counter("database", "tail", "busy")
                           if metrics else None)
        if path != ":memory:":
            # it never waits in a statement: `_begin_tail` takes the
            # write lock at BEGIN and counts a wait there
            self._tail_conn = self._connect(path, timeout=0)

    @staticmethod
    def _connect(path: str, **kw) -> sqlite3.Connection:
        conn = sqlite3.connect(
            path, check_same_thread=False, cached_statements=256, **kw)
        conn.isolation_level = None     # explicit transaction control
        conn.execute("PRAGMA journal_mode=WAL")
        conn.execute("PRAGMA synchronous=NORMAL")
        return conn

    # ---------------------------------------------------------------- core --
    def execute(self, sql: str, params: Iterable[Any] = (),
                rows: int = 1) -> sqlite3.Cursor:
        """`rows`: what the statement counts for in `database.query.exec`."""
        if self._tail_owner is threading.current_thread():
            self._tail_statements += rows
            return self._tail_conn.execute(sql, tuple(params))
        self._completion_barrier(sql)
        with self._lock:
            if self._query_meter:
                self._query_meter.mark(rows)
            return self._conn.execute(sql, tuple(params))

    def executemany(self, sql: str, rows: Iterable[Iterable[Any]]) -> None:
        rows = list(rows)
        if self._tail_owner is threading.current_thread():
            self._tail_statements += len(rows)
            self._tail_conn.executemany(sql, rows)
            return
        self._completion_barrier(sql)
        with self._lock:
            if self._query_meter:
                # meter per row so batched writes stay visible in the
                # database.query metrics an operator watches
                self._query_meter.mark(len(rows))
            self._conn.executemany(sql, rows)

    def insert_rows(self, sql: str, rows: Iterable[Iterable[Any]]) -> None:
        """The rows of `executemany(sql, rows)` as multi-row VALUES
        statements, `PACKED_INSERT_ROWS` at a time: the same rows in
        the same order, in one `sqlite3_step` a statement instead of
        one a row. Every step hands the GIL away and has to take it
        back, which beside a thread that is busy in Python (the next
        close's apply, when the caller is the completion tail) costs up
        to the interpreter's switch interval each time
        (docs/CLOSE_PIPELINE.md, "Two connections"). Metered a row, as
        `executemany` is."""
        head, _, one = sql.rpartition("VALUES")
        per = max(1, min(PACKED_INSERT_ROWS,
                         self._variable_limit // one.count("?")))
        rows = list(rows)
        for i in range(0, len(rows), per):
            part = rows[i:i + per]
            self.execute(head + "VALUES" + ",".join([one] * len(part)),
                         [v for row in part for v in row], rows=len(part))

    # -------------------------------------------------------- transactions --
    class _TxScope:
        """Nested transaction scope via SAVEPOINTs (reference:
        soci::transaction held open across a ledger close,
        ledger/LedgerManagerImpl.cpp:715-936).

        The session lock is HELD for the whole scope: the ledger-close
        completion worker and the main thread both write through this
        connection, and interleaving statements inside an open
        BEGIN/SAVEPOINT would corrupt the shared depth machinery.  The
        lock is an RLock, so same-thread nesting still works."""

        def __init__(self, db: "Database", begin: str = "BEGIN"):
            self._db = db
            self._begin = begin
            self._done = False

        def __enter__(self):
            db = self._db
            db._lock.acquire()
            try:
                if db._tx_depth == 0:
                    db._conn.execute(self._begin)
                    db._tx_owner = threading.current_thread()
                else:
                    db._conn.execute(f"SAVEPOINT sp{db._tx_depth}")
                db._tx_depth += 1
                self._depth = db._tx_depth
            except BaseException:
                db._lock.release()
                raise
            return self

        def __exit__(self, exc_type, exc, tb):
            db = self._db
            try:
                db._tx_depth -= 1
                if exc_type is None:
                    if db._tx_depth == 0:
                        db._commit(db._conn)
                    else:
                        db._conn.execute(f"RELEASE sp{db._tx_depth}")
                else:
                    if db._tx_depth == 0:
                        db._conn.execute("ROLLBACK")
                    else:
                        db._conn.execute(
                            f"ROLLBACK TO sp{db._tx_depth}")
                        db._conn.execute(f"RELEASE sp{db._tx_depth}")
            finally:
                # even if COMMIT/ROLLBACK itself raised: an outermost
                # scope is over either way, and a stale owner would let
                # this thread bypass the completion barrier forever
                if db._tx_depth == 0:
                    db._tx_owner = None
                db._lock.release()
            return False

    def transaction(self) -> "_TxScope":
        return Database._TxScope(self)

    def writing_transaction(self) -> "_TxScope":
        # the write lock is taken at BEGIN, so another connection's
        # writer is waited out (the busy timeout). Under a deferred
        # BEGIN its commit, landing between the BEGIN and the first
        # write, leaves this one a stale snapshot: SQLITE_BUSY_SNAPSHOT,
        # which no timeout waits out
        return Database._TxScope(self, "BEGIN IMMEDIATE")

    def _commit(self, conn: sqlite3.Connection) -> None:
        if chaos.ENABLED:
            # a simulated commit failure must leave the connection
            # clean: roll back, then raise — exactly what a real failed
            # COMMIT leaves
            try:
                chaos.point("db.commit", db=self.path)
            except BaseException:
                conn.execute("ROLLBACK")
                raise
        conn.execute("COMMIT")

    def _begin_tail(self) -> None:
        conn = self._tail_conn
        try:
            conn.execute("BEGIN IMMEDIATE")
            return
        except sqlite3.OperationalError as exc:
            if exc.sqlite_errorcode & 0xff != sqlite3.SQLITE_BUSY:
                raise
        # another writer holds the file. A close never does: its BEGIN
        # follows its join of this tail, and the next tail is submitted
        # after its COMMIT. Wait for whoever it is, and say so
        if self._tail_busy is not None:
            self._tail_busy.inc()
        log.warning("the close tail's transaction found %s locked by "
                    "another writer", self.path)
        conn.execute(f"PRAGMA busy_timeout={BUSY_TIMEOUT_MS}")
        try:
            conn.execute("BEGIN IMMEDIATE")
        finally:
            conn.execute("PRAGMA busy_timeout=0")

    @contextmanager
    def _tail_scope(self):
        """One transaction on the tail's connection: the statements of
        the thread inside it go there, with no barrier (what the tail
        reads of its own is in its own transaction) and without the
        first connection's lock. Not nested."""
        with self._tail_lock:
            self._begin_tail()
            self._tail_statements = 0
            self._tail_owner = threading.current_thread()
            try:
                yield
            except BaseException:
                self._tail_owner = None
                self._tail_conn.execute("ROLLBACK")
                raise
            self._tail_owner = None
            self._commit(self._tail_conn)
        if self._query_meter:
            with self._lock:        # the meter is the first connection's
                self._query_meter.mark(self._tail_statements)

    def tail_transaction(self):
        """The completion tail's one transaction (a ledger's history
        rows and its LAST_CLOSE_COMPLETED marker): through the second
        connection where there is one, so that the closing thread's
        reads do not queue behind it."""
        if self._tail_conn is None:
            return self.transaction()
        return self._tail_scope()

    # ---------------------------------------------------------------- misc --
    def close(self) -> None:
        with self._lock:
            self._conn.close()
        if self._tail_conn is not None:
            with self._tail_lock:
                self._tail_conn.close()
