"""Golden tx-meta baseline testing.

Reference: the `--check-test-tx-meta` CI mechanism (test/test.h:23-28,
baselines checked in under test-tx-meta-baseline-current/): the XDR
TransactionMeta produced by applying a fixed scenario is hashed and
compared against a checked-in baseline, so any unintended change to apply
semantics (fees, entry changes, meta encoding) is caught as a diff.

Regenerate after an *intended* semantic change with:
    UPDATE_TX_META_BASELINE=1 python -m pytest tests/test_tx_meta_baseline.py
"""

import json
import os

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_standalone_app as m1
from txtest_utils import (make_asset, native, op_change_trust,
                          op_create_account, op_manage_data, op_payment,
                          op_set_options)

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "testdata",
                             "tx_meta_baselines.json")
UPDATE = os.environ.get("UPDATE_TX_META_BASELINE") == "1"


def _collect_app():
    """App whose meta stream is captured in-memory."""
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    app = Application.create(clock, cfg)
    metas = []
    app.ledger_manager.meta_stream = metas.append
    app.start()
    return app, metas


def _meta_hashes(app, metas):
    """Per-tx sha256 of the XDR TransactionMeta, in apply order. The
    stream is fed by the closes' completion tails: its consumer joins."""
    app.herder.join_completion()
    out = []
    for meta in metas:
        v = meta.value
        for trm in v.txProcessing:
            out.append(sha256(trm.txApplyProcessing.to_bytes()).hex())
    return out


def _submit_ok(app, frame):
    r = m1.submit(app, frame)
    assert r.get("status") == "PENDING", r
    return r


def _check(name: str, hashes):
    assert hashes, "scenario produced no tx meta"
    baselines = {}
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            baselines = json.load(f)
    if UPDATE:
        baselines[name] = hashes
        os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=1, sort_keys=True)
        pytest.skip("baseline regenerated")
    assert name in baselines, (
        f"no baseline for {name}; run with UPDATE_TX_META_BASELINE=1")
    assert hashes == baselines[name], (
        f"tx meta for {name} diverged from the checked-in baseline; if the "
        "change is intended, regenerate with UPDATE_TX_META_BASELINE=1")


def test_classic_scenario_meta_is_stable():
    app, metas = _collect_app()
    try:
        master = m1.master_account(app)
        a = m1.AppAccount(app, SecretKey.from_seed(sha256(b"meta-a")))
        b = m1.AppAccount(app, SecretKey.from_seed(sha256(b"meta-b")))
        _submit_ok(app, master.tx([
            op_create_account(a.account_id, 500_0000000),
            op_create_account(b.account_id, 500_0000000)]))
        app.manual_close()
        a.sync_seq(); b.sync_seq()
        usd = make_asset(b"USD", master.account_id)
        _submit_ok(app, a.tx([op_change_trust(usd, 2**62),
                              op_manage_data(b"k1", b"v1"),
                              op_set_options(homeDomain=b"example.com")]))
        _submit_ok(app, b.tx([op_payment(a.muxed, 1234567)]))
        app.manual_close()
        _submit_ok(app, master.tx([op_payment(a.muxed, 42, asset=usd)]))
        app.manual_close()
        _check("classic-v1", _meta_hashes(app, metas))
    finally:
        app.shutdown()


@pytest.mark.parametrize("build,golden", [
    ("scvm", "soroban-upload-v1"),
    ("wasm", "soroban-upload-wasm-v1"),
])
def test_soroban_scenario_meta_is_stable(build, golden):
    import test_soroban as sb
    # pin the contract build: sb.COUNTER_CODE is swapped by test_soroban's
    # parametrized fixture, so it must be set explicitly here
    sb.COUNTER_CODE = sb.CODE_BUILDS[build]
    app, metas = _collect_app()
    try:
        master = m1.master_account(app)
        from stellar_core_tpu.xdr.ledger_entries import LedgerKey
        code_key = LedgerKey.contract_code(sb.wasm_hash())
        frame = sb.soroban_tx(app, master, sb.upload_op(), [], [code_key])
        r = m1.submit(app, frame)
        assert r["status"] == "PENDING", r
        app.manual_close()
        _check(golden, _meta_hashes(app, metas))
    finally:
        app.shutdown()


def test_dex_scenario_meta_is_stable():
    """Crossing offers + a fee-bump exercise OfferExchange rounding and
    the fee-bump meta shape; pins their XDR meta bytes."""
    from txtest_utils import (op_manage_sell_offer, op_manage_buy_offer)
    from stellar_core_tpu.xdr.ledger_entries import Price
    app, metas = _collect_app()
    try:
        master = m1.master_account(app)
        a = m1.AppAccount(app, SecretKey.from_seed(sha256(b"dex-a")))
        b = m1.AppAccount(app, SecretKey.from_seed(sha256(b"dex-b")))
        _submit_ok(app, master.tx([
            op_create_account(a.account_id, 500_0000000),
            op_create_account(b.account_id, 500_0000000)]))
        app.manual_close()
        a.sync_seq(); b.sync_seq()
        usd = make_asset(b"USD", master.account_id)
        _submit_ok(app, a.tx([op_change_trust(usd, 2**62)]))
        _submit_ok(app, b.tx([op_change_trust(usd, 2**62)]))
        app.manual_close()
        _submit_ok(app, master.tx([op_payment(b.muxed, 1_000_0000, usd)]))
        app.manual_close()
        # a sells native for USD; b's buy crosses it
        _submit_ok(app, a.tx([op_manage_sell_offer(
            native(), usd, 100_0000, Price(n=1, d=2), 0)]))
        app.manual_close()
        _submit_ok(app, b.tx([op_manage_buy_offer(
            usd, native(), 50_0000, Price(n=2, d=1), 0)]))
        app.manual_close()
        # the crossing really happened
        row = app.database.query_one("SELECT COUNT(*) FROM offers", ())
        assert row[0] <= 1
        _check("dex-v1", _meta_hashes(app, metas))
    finally:
        app.shutdown()
