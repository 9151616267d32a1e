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


def test_stepwise_upgrade_v1_to_current(tmp_path):
    path = str(tmp_path / "node.db")
    db = Database(path)
    db.initialize()
    assert db.get_schema_version() == SCHEMA_VERSION == 3
    _downgrade_to_v1(db)
    assert db.get_schema_version() == 1
    assert "histbytxid" not in _index_names(db)

    db.upgrade_to_current_schema()
    assert db.get_schema_version() == SCHEMA_VERSION
    names = _index_names(db)
    for stmt in SCHEMA_V2_STATEMENTS:
        idx = stmt.split("EXISTS ")[1].split(" ")[0]
        assert idx in names, idx
    # v3: the durable publish queue table exists again
    assert db.query_one(
        "SELECT name FROM sqlite_master WHERE type='table' "
        "AND name='publishqueue'") is not None
    db.close()


def test_node_upgrades_old_db_on_start(tmp_path):
    """A node opening a v1-era database upgrades it in place
    (reference: Database ctor applying pending schema upgrades)."""
    path = str(tmp_path / "node.db")
    cfg = get_test_config()
    cfg.DATABASE = f"sqlite3://{path}"
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    lcl = app.ledger_manager.get_last_closed_ledger_num()
    _downgrade_to_v1(app.database)
    app.shutdown()

    cfg2 = get_test_config()
    cfg2.DATABASE = f"sqlite3://{path}"
    cfg2.BUCKET_DIR_PATH = cfg.BUCKET_DIR_PATH
    cfg2.NETWORK_PASSPHRASE = cfg.NETWORK_PASSPHRASE
    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg2)
    app2.start()
    try:
        assert app2.database.get_schema_version() == SCHEMA_VERSION
        assert "histbytxid" in _index_names(app2.database)
        assert app2.ledger_manager.get_last_closed_ledger_num() == lcl
    finally:
        app2.shutdown()


def test_upgrade_db_command(tmp_path):
    from stellar_core_tpu.main.command_line import main as cli_main
    path = str(tmp_path / "node.db")
    db = Database(path)
    db.initialize()
    _downgrade_to_v1(db)
    db.close()
    conf = tmp_path / "node.cfg"
    conf.write_text(f'DATABASE = "sqlite3://{path}"\n')
    assert cli_main(["--conf", str(conf), "upgrade-db"]) == 0
    db = Database(path)
    assert db.get_schema_version() == SCHEMA_VERSION
    db.close()


def test_newer_schema_refused(tmp_path):
    db = Database(str(tmp_path / "node.db"))
    db.initialize()
    db.put_schema_version(SCHEMA_VERSION + 1)
    with pytest.raises(RuntimeError, match="newer than supported"):
        db.upgrade_to_current_schema()
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
