"""PostgreSQL backend tests (reference: database/test/DatabaseTests.cpp
dual-backend runs).

The dialect-translation layer and the libpq binding surface are tested
unconditionally. The integration tier (connect, prepared statements,
transactions, node boot + ledger closes, restart) targets a real server
when POSTGRES_TEST_URI is set; otherwise it runs against the in-repo
wire-protocol stub (db/pg_stub.py), so the binding's network paths are
exercised in every environment — note stub runs are protocol-level
coverage, not real-postgres coverage."""

import os

import pytest

from stellar_core_tpu.db.database import create_database
from stellar_core_tpu.db.postgres import translate
from stellar_core_tpu.db.libpq import PostgresError, load_libpq

PG_URI = os.environ.get("POSTGRES_TEST_URI", "")


# ------------------------------------------------------------ translation ---
def test_translate_placeholders():
    assert translate("SELECT entry FROM accounts WHERE key=?").sql == \
        "SELECT entry FROM accounts WHERE key=$1"
    assert translate(
        "SELECT a FROM t WHERE x=? AND y=? LIMIT ? OFFSET ?").sql \
        == "SELECT a FROM t WHERE x=$1 AND y=$2 LIMIT $3 OFFSET $4"


def test_translate_upsert():
    t = translate("INSERT OR REPLACE INTO accounts "
                  "(key, entry, lastmodified) VALUES (?,?,?)")
    assert t.sql == ("INSERT INTO accounts (key, entry, lastmodified) "
                     "VALUES ($1,$2,$3) ON CONFLICT (key) "
                     "DO UPDATE SET entry=EXCLUDED.entry, "
                     "lastmodified=EXCLUDED.lastmodified")
    assert not t.pre_deletes


def test_translate_upsert_composite_key():
    t = translate("INSERT OR REPLACE INTO txhistory "
                  "(txid, ledgerseq, txindex, txbody, txresult, txmeta) "
                  "VALUES (?,?,?,?,?,?)").sql
    assert "ON CONFLICT (ledgerseq, txindex)" in t
    assert "txid=EXCLUDED.txid" in t


def test_translate_upsert_all_key_columns():
    t = translate("INSERT OR REPLACE INTO ban (nodeid) VALUES (?)").sql
    assert t.endswith("ON CONFLICT (nodeid) DO NOTHING")


def test_translate_secondary_unique_predeletes():
    """sqlite OR REPLACE evicts rows conflicting on ANY unique index;
    the postgres translation must pre-delete on the secondary ones
    (ledgerheaders.ledgerseq, offers.offerid)."""
    t = translate("INSERT OR REPLACE INTO ledgerheaders "
                  "(ledgerhash, prevhash, ledgerseq, closetime, data) "
                  "VALUES (?,?,?,?,?)")
    assert len(t.pre_deletes) == 1
    dsql, idxs = t.pre_deletes[0]
    assert dsql.startswith("DELETE FROM ledgerheaders WHERE ledgerseq=$1")
    assert "NOT (ledgerhash=$2)" in dsql
    assert idxs == (2, 0)            # ledgerseq pos, ledgerhash pos
    t2 = translate("INSERT OR REPLACE INTO offers (key, entry, "
                   "lastmodified, sellerid, offerid, sellingasset, "
                   "buyingasset, pricen, priced, price) "
                   "VALUES (?,?,?,?,?,?,?,?,?,?)")
    assert t2.pre_deletes[0][1] == (4, 0)   # offerid pos, key pos


def test_translate_ddl_types():
    t = translate("CREATE TABLE IF NOT EXISTS x ("
                  "key BLOB PRIMARY KEY, n INTEGER, p REAL)").sql
    assert "BYTEA" in t and "BIGINT" in t and "DOUBLE PRECISION" in t
    assert "BLOB" not in t and "INTEGER" not in t


def test_translate_pragma_is_noop():
    assert translate("PRAGMA journal_mode=WAL").sql is None


def test_every_schema_statement_translates():
    from stellar_core_tpu.db.database import schema_statements
    for stmt in schema_statements():
        t = translate(stmt).sql
        assert t is not None and "BLOB" not in t and "?" not in t


def test_schema_v4_step_translates_as_it_stands():
    """The step that drops the two txid indexes is the same statements
    on postgres, which has `DROP INDEX IF EXISTS` itself; the backend
    takes it through the shared `_apply_schema_upgrade`."""
    from stellar_core_tpu.db.database import (SCHEMA_V4_STATEMENTS,
                                              SchemaMixin)
    from stellar_core_tpu.db.postgres import PostgresDatabase
    for stmt, want in zip(SCHEMA_V4_STATEMENTS, (
            "DROP INDEX IF EXISTS histbytxid",
            "DROP INDEX IF EXISTS feehistbytxid"), strict=True):
        t = translate(stmt)
        assert (t.sql, t.pre_deletes, t.n_params) == (want, [], 0)
    assert PostgresDatabase._apply_schema_upgrade is \
        SchemaMixin._apply_schema_upgrade


def test_every_insert_or_replace_in_tree_has_conflict_keys():
    """Every INSERT OR REPLACE the node ever issues must be
    translatable — scan the source tree for table names."""
    import re
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent / \
        "stellar_core_tpu"
    pat = re.compile(r"INSERT OR REPLACE INTO (\w+)")
    from stellar_core_tpu.db.database import TABLE_CONFLICT_KEYS
    tables = set()
    for p in root.rglob("*.py"):
        for chunk in pat.findall(p.read_text()):
            tables.add(chunk.lower())
    # source splits strings: also check the known-SQL builders directly
    assert tables, "scan found no INSERT OR REPLACE statements"
    missing = tables - set(TABLE_CONFLICT_KEYS)
    assert not missing, f"tables without conflict keys: {missing}"


# ---------------------------------------------------------------- binding ---
def test_libpq_loads():
    lib = load_libpq()
    assert lib is not None


def test_connect_failure_is_clean():
    from stellar_core_tpu.db.postgres import PostgresDatabase
    with pytest.raises(PostgresError, match="connection failed"):
        PostgresDatabase(
            "postgresql://nouser@127.0.0.1:1/nodb?connect_timeout=1")


def test_factory_selects_backend():
    from stellar_core_tpu.main import get_test_config
    cfg = get_test_config()
    db = create_database(cfg)
    assert type(db).__name__ == "Database"
    db.close()
    cfg.DATABASE = "postgresql://x@127.0.0.1:1/y?connect_timeout=1"
    with pytest.raises(PostgresError):
        create_database(cfg)
    cfg.DATABASE = "mysql://nope"
    with pytest.raises(ValueError, match="unsupported DATABASE"):
        create_database(cfg)


# -------------------------------------------------------------- integration ---
# POSTGRES_TEST_URI targets a real server when one exists; otherwise the
# hermetic wire-protocol stub (db/pg_stub.py) serves the same tests so
# the libpq binding's connect/prepared/transaction paths always run
#.


@pytest.fixture
def pg_uri():
    if PG_URI:
        yield PG_URI
        return
    from stellar_core_tpu.db.pg_stub import PGStubServer
    srv = PGStubServer().start()   # fresh store per test, like new-db
    try:
        yield srv.url()
    finally:
        srv.stop()


def test_v3_database_upgrades_to_v4_on_postgres(pg_uri):
    """The v3 -> v4 step through the postgres facade and libpq: the
    version moves, the statements are accepted, the rows stay."""
    from stellar_core_tpu.db.database import SCHEMA_VERSION
    from stellar_core_tpu.db.postgres import PostgresDatabase
    db = PostgresDatabase(pg_uri)
    try:
        # the three tables the step and its version touch (the whole
        # DDL is 40 round trips; test_node_boots_... runs it)
        from stellar_core_tpu.db.database import schema_statements
        for stmt in schema_statements():
            if stmt.startswith(tuple(
                    f"CREATE TABLE IF NOT EXISTS {t} " for t in (
                        "storestate", "txhistory", "txfeehistory"))):
                db.execute(stmt)
        db.execute("CREATE INDEX IF NOT EXISTS histbytxid "
                   "ON txhistory (txid)")
        db.execute("CREATE INDEX IF NOT EXISTS feehistbytxid "
                   "ON txfeehistory (txid)")
        db.put_schema_version(3)
        db.execute(
            "INSERT OR REPLACE INTO txhistory "
            "(txid, ledgerseq, txindex, txbody, txresult, txmeta) "
            "VALUES (?,?,?,?,?,?)", (b"h" * 32, 7, 0, b"b", b"r", b"m"))
        db.upgrade_to_current_schema()
        assert db.get_schema_version() == SCHEMA_VERSION == 4
        rows = db.query_all(
            "SELECT txresult FROM txhistory WHERE txid=?", (b"h" * 32,))
        assert [bytes(r[0]) for r in rows] == [b"r"]
    finally:
        db.close()


def test_stub_binding_roundtrip(pg_uri):
    """connect → DDL → prepared upserts → typed reads → transactions,
    straight through libpq."""
    from stellar_core_tpu.db.database import TABLE_CONFLICT_KEYS
    from stellar_core_tpu.db.postgres import PostgresDatabase
    probe_added = "probe" not in TABLE_CONFLICT_KEYS
    TABLE_CONFLICT_KEYS.setdefault("probe", ("key",))
    db = PostgresDatabase(pg_uri)
    try:
        db.execute("CREATE TABLE IF NOT EXISTS probe "
                   "(key BLOB PRIMARY KEY, num INTEGER, txt TEXT)")
        db.executemany(
            "INSERT OR REPLACE INTO probe (key, num, txt) VALUES (?,?,?)",
            [(bytes([i]) * 8, i * 10, f"row{i}") for i in range(5)])
        rows = db.execute(
            "SELECT key, num, txt FROM probe ORDER BY num")
        got = rows.fetchall()
        assert got[0] == (b"\x00" * 8, 0, "row0")
        assert got[4] == (b"\x04" * 8, 40, "row4")
        # 8-byte BLOB key equality must survive the binary protocol
        one = db.execute("SELECT num FROM probe WHERE key=?",
                         (b"\x03" * 8,)).fetchone()
        assert one == (30,)
        # upsert updates in place
        db.executemany(
            "INSERT OR REPLACE INTO probe (key, num, txt) VALUES (?,?,?)",
            [(b"\x03" * 8, 77, "updated")])
        assert db.execute("SELECT num, txt FROM probe WHERE key=?",
                          (b"\x03" * 8,)).fetchone() == (77, "updated")
        # transaction rollback
        with pytest.raises(RuntimeError):
            with db.transaction():
                db.execute("UPDATE probe SET num=? WHERE key=?",
                           (999, b"\x03" * 8))
                raise RuntimeError("boom")
        assert db.execute("SELECT num FROM probe WHERE key=?",
                          (b"\x03" * 8,)).fetchone() == (77,)
        # transaction commit
        with db.transaction():
            db.execute("UPDATE probe SET num=? WHERE key=?",
                       (1000, b"\x03" * 8))
        assert db.execute("SELECT num FROM probe WHERE key=?",
                          (b"\x03" * 8,)).fetchone() == (1000,)
        # a NULL in the first row must not drop the OTHER params'
        # declared OIDs (per-element OID 0 in Parse): the 8-byte BYTEA
        # key would be misdecoded as INT8 and the UPDATE silently
        # match nothing
        db.executemany("UPDATE probe SET txt=? WHERE key=?",
                       [(None, b"\x03" * 8), ("two", b"\x02" * 8)])
        assert db.execute("SELECT txt FROM probe WHERE key=?",
                          (b"\x03" * 8,)).fetchone() == (None,)
        assert db.execute("SELECT txt FROM probe WHERE key=?",
                          (b"\x02" * 8,)).fetchone() == ("two",)
        # a position NULL in the whole first batch must get its OID
        # declared by a later batch's value (re-prepare), not stay
        # guess-decoded forever — "12345678" is 8 bytes, the shape the
        # stub would misread as INT8 on an undeclared position
        db.executemany("UPDATE probe SET txt=? WHERE key=?",
                       [(None, b"\x00" * 8), (None, b"\x01" * 8)])
        db.executemany("UPDATE probe SET txt=? WHERE key=?",
                       [("12345678", b"\x01" * 8)])
        assert db.execute("SELECT txt FROM probe WHERE key=?",
                          (b"\x01" * 8,)).fetchone() == ("12345678",)
    finally:
        db.close()
        if probe_added:
            TABLE_CONFLICT_KEYS.pop("probe", None)


def test_node_boots_and_closes_ledgers_on_postgres(pg_uri):
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    import test_standalone_app as m1

    cfg = get_test_config()
    cfg.DATABASE = pg_uri
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg,
                             new_db=True)
    app.start()
    try:
        master = m1.master_account(app)
        from stellar_core_tpu.crypto.keys import SecretKey
        from stellar_core_tpu.xdr.types import PublicKey
        from txtest_utils import op_create_account
        dest = SecretKey.from_seed(b"\x31" * 32)
        frame = master.tx([op_create_account(
            PublicKey.ed25519(dest.public_key().raw), 10**9)])
        r = m1.submit(app, frame)
        assert r["status"] == "PENDING"
        app.manual_close()
        lcl = app.ledger_manager.get_last_closed_ledger_num()
        assert lcl >= 2
    finally:
        app.shutdown()


def test_restart_recovers_lcl_on_postgres(pg_uri, tmp_path):
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    cfg = get_test_config()
    cfg.DATABASE = pg_uri
    # buckets must outlive the first Application for assume-state
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg,
                             new_db=True)
    app.start()
    app.manual_close()
    lcl = app.ledger_manager.get_last_closed_ledger_num()
    lcl_hash = app.ledger_manager.get_last_closed_ledger_hash()
    app.shutdown()

    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app2.start()
    try:
        assert app2.ledger_manager.get_last_closed_ledger_num() == lcl
        assert app2.ledger_manager.get_last_closed_ledger_hash() == lcl_hash
    finally:
        app2.shutdown()
