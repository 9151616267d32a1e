"""Stepwise DB schema upgrades (reference: Database.cpp:208-265
MIN_SCHEMA_VERSION -> SCHEMA_VERSION with per-step applySchemaUpgrade)
and the opt-in real-PostgreSQL exposure."""

import os

import pytest

from stellar_core_tpu.db.database import (Database, SCHEMA_VERSION,
                                          SCHEMA_V2_STATEMENTS)
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock


def _downgrade_to_v1(db: Database) -> None:
    """Reshape a fresh DB into what a v1-era node left on disk."""
    for name in ("histbytxid", "feehistbytxid", "scpenvsbyseq"):
        db.execute(f"DROP INDEX IF EXISTS {name}")
    db.execute("DROP TABLE IF EXISTS publishqueue")
    db.put_schema_version(1)


def _index_names(db: Database):
    return {r[0] for r in db.query_all(
        "SELECT name FROM sqlite_master WHERE type='index'")}


TXID_INDEXES = {"histbytxid", "feehistbytxid"}


def _downgrade_to_v3(db: Database) -> None:
    """What a v3 node left on disk: the two txid indexes, version 3."""
    db.execute("CREATE INDEX IF NOT EXISTS histbytxid ON txhistory (txid)")
    db.execute("CREATE INDEX IF NOT EXISTS feehistbytxid "
               "ON txfeehistory (txid)")
    db.put_schema_version(3)


def _history_rows(n: int, seq: int = 7):
    """`n` rows a table in the shape the close's tail writes them."""
    tx = [(bytes([i]) * 32, seq, i, b"body%d" % i, b"result%d" % i,
           b"meta%d" % i) for i in range(n)]
    fee = [(bytes([i]) * 32, seq, i, b"changes%d" % i) for i in range(n)]
    return tx, fee


def _store_history(db: Database, tx, fee) -> None:
    db.executemany(
        "INSERT OR REPLACE INTO txhistory "
        "(txid, ledgerseq, txindex, txbody, txresult, txmeta) "
        "VALUES (?,?,?,?,?,?)", tx)
    db.executemany(
        "INSERT OR REPLACE INTO txfeehistory "
        "(txid, ledgerseq, txindex, txchanges) VALUES (?,?,?,?)", fee)


def _dump(db: Database) -> dict:
    return {t: [tuple(bytes(c) if isinstance(c, (bytes, memoryview))
                      else c for c in r) for r in db.query_all(
                f"SELECT * FROM {t} ORDER BY ledgerseq, txindex")]
            for t in ("txhistory", "txfeehistory")}


def test_stepwise_upgrade_v1_to_current(tmp_path):
    path = str(tmp_path / "node.db")
    db = Database(path)
    db.initialize()
    assert db.get_schema_version() == SCHEMA_VERSION == 4
    _downgrade_to_v1(db)
    assert db.get_schema_version() == 1
    assert "histbytxid" not in _index_names(db)

    db.upgrade_to_current_schema()
    assert db.get_schema_version() == SCHEMA_VERSION
    names = _index_names(db)
    for stmt in SCHEMA_V2_STATEMENTS:
        idx = stmt.split("EXISTS ")[1].split(" ")[0]
        assert idx in names, idx
    # v4: the ladder never leaves a txid index behind, whether or not
    # step 2 made one
    assert not TXID_INDEXES & names
    # v3: the durable publish queue table exists again
    assert db.query_one(
        "SELECT name FROM sqlite_master WHERE type='table' "
        "AND name='publishqueue'") is not None
    db.close()


def _reopened_node(tmp_path, downgrade, new_db):
    """Close one ledger into a file-backed node, reshape its database
    with `downgrade`, shut down, and start a second node on the file.
    Returns (second node, the first node's LCL)."""
    path = str(tmp_path / "node.db")
    cfg = get_test_config()
    cfg.DATABASE = f"sqlite3://{path}"
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    lcl = app.ledger_manager.get_last_closed_ledger_num()
    downgrade(app.database)
    app.shutdown()

    cfg2 = get_test_config()
    cfg2.DATABASE = f"sqlite3://{path}"
    cfg2.BUCKET_DIR_PATH = cfg.BUCKET_DIR_PATH
    cfg2.NETWORK_PASSPHRASE = cfg.NETWORK_PASSPHRASE
    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg2,
                              new_db=new_db)
    app2.start()
    return app2, lcl


def test_node_upgrades_old_db_on_start(tmp_path):
    """A node opening a v1-era database upgrades it in place
    (reference: Database ctor applying pending schema upgrades)."""
    app2, lcl = _reopened_node(tmp_path, _downgrade_to_v1, new_db=True)
    try:
        assert app2.database.get_schema_version() == SCHEMA_VERSION
        assert not TXID_INDEXES & _index_names(app2.database)
        assert app2.ledger_manager.get_last_closed_ledger_num() == lcl
    finally:
        app2.shutdown()


@pytest.mark.parametrize("was", [1, 3])
def test_upgrade_db_command(tmp_path, capsys, was):
    from stellar_core_tpu.main.command_line import main as cli_main
    path = str(tmp_path / "node.db")
    db = Database(path)
    db.initialize()
    {1: _downgrade_to_v1, 3: _downgrade_to_v3}[was](db)
    db.close()
    conf = tmp_path / "node.cfg"
    conf.write_text(f'DATABASE = "sqlite3://{path}"\n')
    assert cli_main(["--conf", str(conf), "upgrade-db"]) == 0
    assert f"schema version {was} -> 4" in capsys.readouterr().out
    db = Database(path)
    assert db.get_schema_version() == SCHEMA_VERSION
    assert not TXID_INDEXES & _index_names(db)
    db.close()


def test_newer_schema_refused(tmp_path):
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    db.put_schema_version(SCHEMA_VERSION + 1)
    with pytest.raises(RuntimeError, match="newer than supported"):
        db.upgrade_to_current_schema()
    db.close()


# ------------------------------------------- v4: the txid indexes go --

def test_fresh_database_has_one_index_a_history_table(tmp_path):
    """A fresh database is v4 and keeps, on each of the two tables the
    close's tail fills, the automatic (ledgerseq, txindex) index alone:
    no index on a txid column anywhere."""
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    assert db.get_schema_version() == 4
    for table in ("txhistory", "txfeehistory"):
        rows = db.query_all(
            "SELECT name, sql FROM sqlite_master WHERE type='index' "
            "AND tbl_name=?", (table,))
        assert [r[0] for r in rows] == [f"sqlite_autoindex_{table}_1"]
        cols = [r[2] for r in db.query_all(
            f"PRAGMA index_info(sqlite_autoindex_{table}_1)")]
        assert cols == ["ledgerseq", "txindex"]
    assert not any("txid" in (r[0] or "") for r in db.query_all(
        "SELECT sql FROM sqlite_master WHERE type='index'"))
    db.close()


def test_v3_database_upgrades_in_place_with_every_row(tmp_path):
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    _downgrade_to_v3(db)
    _store_history(db, *_history_rows(40))
    before = _dump(db)
    assert TXID_INDEXES <= _index_names(db)
    db.close()

    db = Database(str(tmp_path / "node.db"))
    db.upgrade_to_current_schema()
    assert db.get_schema_version() == 4
    assert not TXID_INDEXES & _index_names(db)
    assert "scpenvsbyseq" in _index_names(db)
    after = _dump(db)
    assert after == before
    assert len(after["txhistory"]) == len(after["txfeehistory"]) == 40
    db.close()


def test_v4_step_twice_is_a_no_op(tmp_path):
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    _downgrade_to_v3(db)
    _store_history(db, *_history_rows(5))
    db._apply_schema_upgrade(4)
    names, rows = _index_names(db), _dump(db)
    db._apply_schema_upgrade(4)
    assert _index_names(db) == names and _dump(db) == rows
    assert not TXID_INDEXES & names
    db.close()


def test_v2_database_with_the_indexes_ends_at_v4_without(tmp_path):
    """The rung between the v1 and the v3 tests: a v2 file an older
    tree wrote has the two indexes and no publish queue."""
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    _downgrade_to_v3(db)
    db.execute("DROP TABLE publishqueue")
    db.put_schema_version(2)
    db.upgrade_to_current_schema()
    assert db.get_schema_version() == 4
    names = _index_names(db)
    assert not TXID_INDEXES & names and "scpenvsbyseq" in names
    assert db.query_one("SELECT count(*) FROM publishqueue")[0] == 0
    db.close()


def test_new_db_over_a_v3_file_drops_the_indexes(tmp_path):
    """`initialize()` keeps tables that exist (IF NOT EXISTS); it must
    not stamp v4 on a file that still has the v3 indexes."""
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    _downgrade_to_v3(db)
    db.initialize()
    assert db.get_schema_version() == 4
    assert not TXID_INDEXES & _index_names(db)
    db.close()


def test_node_opening_a_v3_file_upgrades_and_closes(tmp_path):
    app2, lcl = _reopened_node(tmp_path, _downgrade_to_v3, new_db=False)
    try:
        assert app2.database.get_schema_version() == 4
        assert not TXID_INDEXES & _index_names(app2.database)
        app2.manual_close()
        assert app2.ledger_manager.get_last_closed_ledger_num() == lcl + 1
    finally:
        app2.shutdown()


# ------------------------------- v4: the readers are none the worse --

def _plan(db: Database, sql: str, params) -> str:
    return " | ".join(r[3] for r in db.query_all(
        "EXPLAIN QUERY PLAN " + sql, params))


def _issues(module, sql: str) -> bool:
    """Whether `sql` stands in `module`'s source, however the source
    splits the string over lines and literals."""
    import inspect
    import re

    def bare(text):
        return re.sub(r'f?"|\s', "", text)
    return bare(sql) in bare(inspect.getsource(module))


def test_publish_reads_a_ledgers_transactions_by_ledgerseq(tmp_path):
    """history/manager.py's one read of txhistory searches the table's
    own key and scans nothing."""
    from stellar_core_tpu.history import manager
    sql = ("SELECT txbody, txresult FROM txhistory WHERE ledgerseq=? "
           "ORDER BY txindex")
    assert _issues(manager, sql)
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    _store_history(db, *_history_rows(8))
    plan = _plan(db, sql, (7,))
    assert "SEARCH" in plan and "ledgerseq=?" in plan, plan
    assert "SCAN" not in plan and "TEMP B-TREE" not in plan, plan
    db.close()


@pytest.mark.parametrize("table,key", [
    ("txhistory", "ledgerseq"), ("txfeehistory", "ledgerseq"),
    ("txsethistory", "rowid"),      # its ledgerseq is the rowid
    ("scphistory", "ledgerseq")])
def test_maintainer_deletes_by_ledgerseq(tmp_path, table, key):
    from stellar_core_tpu.main import maintainer
    sql = "DELETE FROM {table} WHERE ledgerseq >= ? AND ledgerseq < ?"
    assert _issues(maintainer, sql) and _issues(maintainer, f'"{table}"')
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    plan = _plan(db, sql.format(table=table), (1, 5))
    assert "SEARCH" in plan and f"{key}>? AND {key}<?" in plan, plan
    assert "SCAN" not in plan, plan
    db.close()


@pytest.mark.parametrize("table,column", [
    ("txhistory", "txresult"), ("txhistory", "txmeta"),
    ("txhistory", "txbody"), ("txfeehistory", "txchanges")])
def test_select_by_txid_still_answers_on_v4(tmp_path, table, column):
    """What the selects of test_soroban, test_sac, test_env_abi and
    test_protocol_transition rely on: with no index a `WHERE txid=?`
    is a scan, and returns the same row."""
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    tx, fee = _history_rows(12)
    _store_history(db, tx, fee)
    want = {"txresult": tx[9][4], "txmeta": tx[9][5], "txbody": tx[9][3],
            "txchanges": fee[9][3]}[column]
    rows = db.query_all(
        f"SELECT {column} FROM {table} WHERE txid=?", (tx[9][0],))
    assert [bytes(r[0]) for r in rows] == [want]
    assert "SCAN" in _plan(
        db, f"SELECT {column} FROM {table} WHERE txid=?", (tx[9][0],))
    db.close()


# ------------------------------------------------- real-postgres opt-in --

@pytest.mark.skipif(
    not os.environ.get("PGHOST"),
    reason="real-PostgreSQL exposure needs PGHOST (plus PGUSER/PGDATABASE"
           "/PGPASSWORD as applicable) pointing at a live server; the "
           "hermetic suite otherwise covers the dialect through the "
           "in-repo wire stub only")
def test_postgres_against_real_server():
    """The dialect translator (upsert rewriting, $n placeholders,
    secondary-unique pre-DELETEs) against a real PostgreSQL — the
    reference CIs this way (ci-build.sh:173-174)."""
    from stellar_core_tpu.db.postgres import PostgresDatabase
    host = os.environ["PGHOST"]
    user = os.environ.get("PGUSER", "postgres")
    dbname = os.environ.get("PGDATABASE", "postgres")
    pw = os.environ.get("PGPASSWORD", "")
    uri = f"postgresql://{user}:{pw}@{host}:" \
          f"{os.environ.get('PGPORT', '5432')}/{dbname}"
    db = PostgresDatabase(uri)
    try:
        db.initialize()
        assert db.get_schema_version() == SCHEMA_VERSION
        # upsert path (INSERT OR REPLACE translation) + secondary-unique
        # pre-delete: two headers sharing a ledgerseq must not collide
        db.execute(
            "INSERT OR REPLACE INTO ledgerheaders "
            "(ledgerhash, prevhash, ledgerseq, closetime, data) "
            "VALUES (?,?,?,?,?)", (b"h1", b"p", 7, 1, b"d1"))
        db.execute(
            "INSERT OR REPLACE INTO ledgerheaders "
            "(ledgerhash, prevhash, ledgerseq, closetime, data) "
            "VALUES (?,?,?,?,?)", (b"h2", b"p", 7, 2, b"d2"))
        rows = db.query_all(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
            (7,))
        assert [bytes(r[0]) for r in rows] == [b"h2"]
    finally:
        db.close()
