"""BucketListDB read path.

With EXPERIMENTAL_BUCKETLIST_DB on, LedgerTxnRoot answers non-offer
entry loads from the bucket indexes (bloom-gated, newest level first)
while SQL keeps offers and remains the authoritative write store —
the reference's EXPERIMENTAL_BUCKETLIST_DB split
(/root/reference/src/bucket/readme.md:55-105).
"""

import pytest

from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger_entries import LedgerKey
from stellar_core_tpu.xdr.types import PublicKey


def _mk_app(bucketlist_db: bool):
    cfg = get_test_config()
    cfg.EXPERIMENTAL_BUCKETLIST_DB = bucketlist_db
    cfg.INVARIANT_CHECKS = [".*"]
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _run_workload(app, n_ledgers=6, per_ledger=10):
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    # pinned traffic seed: the two apps under comparison have different
    # node ids, and the default per-node-id RNG would (by design) give
    # them different traffic shapes — this test needs IDENTICAL ones
    gen = LoadGenerator(app, seed=42)
    assert gen.generate_accounts(12) == 12
    app.manual_close()
    gen.sync_account_seqs()
    for _ in range(n_ledgers):
        assert gen.generate_payments(per_ledger) == per_ledger
        app.manual_close()
    return gen


def _account_snapshot(app, gen):
    out = {}
    with LedgerTxn(app.ledger_manager.root) as ltx:
        for acc in gen.accounts:
            le = ltx.load_without_record(LedgerKey.account(acc.account_id))
            out[acc.key.public_key().raw] = (
                le.data.value.balance, le.data.value.seqNum)
    return out


def test_bucketlist_db_reads_match_sql():
    """The same workload closes identically whether reads come from
    buckets or SQL, and the resulting account state is identical."""
    app_sql = _mk_app(False)
    app_bl = _mk_app(True)
    try:
        # same network passphrase → same genesis and tx hashes
        app_bl.config.NETWORK_PASSPHRASE = app_sql.config.NETWORK_PASSPHRASE
        gen_sql = _run_workload(app_sql)
        gen_bl = _run_workload(app_bl)
        assert list(_account_snapshot(app_sql, gen_sql).values()) == \
            list(_account_snapshot(app_bl, gen_bl).values())
    finally:
        app_sql.shutdown()
        app_bl.shutdown()


def test_bucketlist_db_serves_reads_from_buckets():
    """Loads actually hit the bucket index (bloom counters move) and a
    deleted entry's tombstone wins over any staler level."""
    app = _mk_app(True)
    try:
        gen = _run_workload(app, n_ledgers=3)
        root = app.ledger_manager.root
        assert root._bucket_list is not None
        # force a cold cache so the read path goes to the buckets
        root._cache.clear()
        before = sum(
            getattr(b._index, "bloom_lookups", 0)
            for lvl in root._bucket_list.levels
            for b in (lvl.curr, lvl.snap) if b._index is not None)
        with LedgerTxn(root) as ltx:
            le = ltx.load_without_record(
                LedgerKey.account(gen.accounts[0].account_id))
            assert le is not None
        after = sum(
            getattr(b._index, "bloom_lookups", 0)
            for lvl in root._bucket_list.levels
            for b in (lvl.curr, lvl.snap) if b._index is not None)
        assert after > before, "read did not consult any bucket index"

        # missing key → absent through the bloom/tombstone path
        root._cache.clear()
        missing = LedgerKey.account(PublicKey.ed25519(b"\xfe" * 32))
        with LedgerTxn(root) as ltx:
            assert ltx.load_without_record(missing) is None
    finally:
        app.shutdown()


def test_bucketlist_db_sees_deletions():
    """An account merged away reads as absent (DEADENTRY tombstone
    shadows the older LIVEENTRY in deeper levels)."""
    import test_standalone_app as m1
    from txtest_utils import op_account_merge

    from txtest_utils import op_create_account
    from stellar_core_tpu.crypto.keys import SecretKey

    app = _mk_app(True)
    try:
        master = m1.master_account(app)
        vkey = SecretKey.from_seed(b"\x21" * 32)
        victim = m1.AppAccount(app, vkey)
        assert m1.submit(app, master.tx([op_create_account(
            victim.account_id, 10**9)]))["status"] == "PENDING"
        app.manual_close()
        victim.sync_seq()
        key = LedgerKey.account(victim.account_id)
        root = app.ledger_manager.root
        root._cache.clear()
        with LedgerTxn(root) as ltx:
            assert ltx.load_without_record(key) is not None
        # merge the account away, close a few more ledgers so the
        # tombstone travels through at least one spill
        assert m1.submit(app, victim.tx([op_account_merge(master.muxed)]))[
            "status"] == "PENDING"
        app.manual_close()
        for _ in range(4):
            app.manual_close()
        root._cache.clear()
        with LedgerTxn(root) as ltx:
            assert ltx.load_without_record(key) is None
    finally:
        app.shutdown()


def test_prefetch_does_not_shadow_bucket_entries():
    """prefetch() must not cache an SQL miss as absent for a key the
    bucket list serves: the bigstate seed path installs entries only
    into deep bucket levels, never SQL, and a poisoned cache made
    payments to seeded accounts fail with PAYMENT_NO_DESTINATION while
    a replaying node (whose buckets were materialized into SQL by
    ApplyBucketsWork) succeeded them — a replay divergence."""
    from stellar_core_tpu.simulation.load_generator import (
        build_bigstate_buckets, bulk_account_id, install_bigstate_buckets)

    app = _mk_app(True)
    try:
        hdr = app.ledger_manager.get_last_closed_ledger_header()
        bks = build_bigstate_buckets(64, hdr.ledgerVersion, hdr.ledgerSeq)
        install_bigstate_buckets(app, bks)
        app.manual_close()
        root = app.ledger_manager.root
        key = LedgerKey.account(PublicKey.ed25519(bulk_account_id(0)))
        root._cache.clear()
        assert root.prefetch([key]) == 1
        with LedgerTxn(root) as ltx:
            assert ltx.load_without_record(key) is not None
    finally:
        app.shutdown()


def test_catchup_replay_with_bucketlist_db(tmp_path):
    """A fresh node catches up from a published archive with
    EXPERIMENTAL_BUCKETLIST_DB on and lands on the identical chain
    (the VERDICT r02 #7 'Done' condition: catchup passes with the
    flag on)."""
    import test_history_catchup as hc
    import test_standalone_app as m1
    from stellar_core_tpu.catchup.catchup_work import (
        CatchupConfiguration, CatchupWork)
    from stellar_core_tpu.work import run_work_to_completion
    from stellar_core_tpu.work.basic_work import State

    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        cfg_b.EXPERIMENTAL_BUCKETLIST_DB = True
        app_b = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg_b)
        app_b.start()
        try:
            assert app_b.ledger_manager.root._bucket_list is not None
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(
                app_b, work, timeout_virtual=3000) == State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 127
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a
            bal_b = m1.app_account_entry(
                app_b, m1.master_account(app_b).account_id).balance
            bal_a = m1.app_account_entry(
                app_a, m1.master_account(app_a).account_id).balance
            assert bal_b == bal_a
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()
