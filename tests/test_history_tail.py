"""The close's history tail writes the bytes the plain encoding gives.

`LedgerManager._complete_close` builds each transaction's history
artifacts once and serialises them once, through the native codec, for
both sinks (the `txhistory` / `txfeehistory` / `txsethistory` rows and
the debug-meta record). The oracle here encodes per sink and per
transaction instead, through the pure-Python `Writer` path: every row
and every record must be byte-identical, for both meta encodings
(TransactionMetaV2 below protocol 20, V3 from 20 on) and for the
transaction shapes that reach `_encode_tx_meta` differently.
"""

import os

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ledger import ledger_manager as lm_mod
from stellar_core_tpu.ledger.ledger_manager import ledger_header_hash
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.xdr_stream import read_record
from stellar_core_tpu.xdr.ledger import (LedgerCloseMeta, LedgerCloseMetaV0,
                                         LedgerCloseMetaV1,
                                         LedgerEntryChanges,
                                         LedgerHeaderHistoryEntry,
                                         TransactionResultMeta)
from stellar_core_tpu.xdr.runtime import Writer
from stellar_core_tpu.xdr.transaction import (DecoratedSignature,
                                              FeeBumpTransaction,
                                              FeeBumpTransactionEnvelope,
                                              TransactionEnvelope,
                                              _FeeBumpInnerTx, _TxExt)
from stellar_core_tpu.xdr.types import EnvelopeType, ExtensionPoint

import test_soroban as sb
import test_standalone_app as m1
from txtest_utils import op_create_account, op_manage_data, op_payment


# ------------------------------------------------------------- the oracle --

def plain_bytes(value) -> bytes:
    """A Struct/Union through the pure-Python Writer path."""
    w = Writer()
    value._pack(w)
    return bytes(w.buf)


def capture_tail(lm) -> list:
    """Record what every completion tail of `lm` is given."""
    seen = []
    orig = lm._complete_close

    def spy(seq, closed, lcd, applicable, txs, result_pairs, fee_metas,
            tx_metas, upgrade_metas, apply_version, publish):
        seen.append(dict(
            seq=seq, closed=closed, applicable=applicable, txs=list(txs),
            result_pairs=list(result_pairs), fee_metas=list(fee_metas),
            tx_metas=list(tx_metas), upgrade_metas=list(upgrade_metas),
            apply_version=apply_version))
        orig(seq, closed, lcd, applicable, txs, result_pairs, fee_metas,
             tx_metas, upgrade_metas, apply_version, publish)

    lm._complete_close = spy
    return seen


def plain_close_meta(t) -> LedgerCloseMeta:
    """The LedgerCloseMeta of one tail, one `_encode_tx_meta` call per
    transaction for this sink alone."""
    hhe = LedgerHeaderHistoryEntry(
        hash=ledger_header_hash(t["closed"]), header=t["closed"],
        ext=ExtensionPoint(0))
    processing = [
        TransactionResultMeta(
            result=t["result_pairs"][i], feeProcessing=t["fee_metas"][i],
            txApplyProcessing=lm_mod._encode_tx_meta(
                t["tx_metas"][i], t["apply_version"]))
        for i in range(len(t["txs"]))]
    wire = t["applicable"].to_wire()
    if wire.is_generalized:
        return LedgerCloseMeta(1, LedgerCloseMetaV1(
            ext=ExtensionPoint(0), ledgerHeader=hhe, txSet=wire.to_xdr(),
            txProcessing=processing,
            upgradesProcessing=t["upgrade_metas"], scpInfo=[],
            totalByteSizeOfBucketList=0, evictedTemporaryLedgerKeys=[],
            evictedPersistentLedgerEntries=[]))
    return LedgerCloseMeta(0, LedgerCloseMetaV0(
        ledgerHeader=hhe, txSet=wire.to_xdr(), txProcessing=processing,
        upgradesProcessing=t["upgrade_metas"], scpInfo=[]))


def plain_rows(t) -> dict:
    """The three tables' rows of one tail, each column packed on its
    own through the Python path."""
    seq = t["seq"]
    wire = t["applicable"].to_wire()
    tx_rows, fee_rows = [], []
    for i, tx in enumerate(t["txs"]):
        tx_rows.append((
            tx.full_hash(), seq, i, plain_bytes(tx.envelope),
            plain_bytes(t["result_pairs"][i]),
            plain_bytes(lm_mod._encode_tx_meta(t["tx_metas"][i],
                                               t["apply_version"]))))
        w = Writer()
        LedgerEntryChanges.pack(w, t["fee_metas"][i])
        fee_rows.append((tx.full_hash(), seq, i, bytes(w.buf)))
    return {
        "txsethistory": [(seq, 1 if wire.is_generalized else 0,
                          plain_bytes(wire.to_xdr()))],
        "txhistory": tx_rows, "txfeehistory": fee_rows}


def stored_rows(db, seq: int) -> dict:
    def rows(sql):
        return [tuple(bytes(c) if isinstance(c, (bytes, memoryview)) else c
                      for c in r) for r in db.query_all(sql, (seq,))]
    return {
        "txsethistory": rows(
            "SELECT ledgerseq, isgeneralized, txset FROM txsethistory "
            "WHERE ledgerseq=?"),
        "txhistory": rows(
            "SELECT txid, ledgerseq, txindex, txbody, txresult, txmeta "
            "FROM txhistory WHERE ledgerseq=? ORDER BY txindex"),
        "txfeehistory": rows(
            "SELECT txid, ledgerseq, txindex, txchanges FROM txfeehistory "
            "WHERE ledgerseq=? ORDER BY txindex")}


def debug_records(meta_dir: str) -> list:
    out = []
    for name in sorted(os.listdir(meta_dir)):
        if name.endswith(".xdr.gz.tmp"):
            continue        # the open segment's compressed side
        assert name.endswith(".xdr"), name
        with open(os.path.join(meta_dir, name), "rb") as f:
            while True:
                rec = read_record(f)
                if rec is None:
                    break
                out.append(rec)
    return out


# ----------------------------------------------------------- the traffic --

def _fee_bump(app, inner, payer):
    fb = FeeBumpTransaction(
        feeSource=payer.muxed, fee=400,
        innerTx=_FeeBumpInnerTx(EnvelopeType.ENVELOPE_TYPE_TX,
                                inner.envelope.value),
        ext=_TxExt(0))
    env = FeeBumpTransactionEnvelope(tx=fb, signatures=[])
    frame = make_frame(
        TransactionEnvelope(EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP, env),
        app.config.network_id())
    env.signatures = [DecoratedSignature(
        hint=payer.key.public_key().hint(),
        signature=payer.key.sign(frame.contents_hash()))]
    frame.signatures = env.signatures
    return frame


def _payment(app, master):
    return [master.tx([op_payment(master.muxed, 7)])]


def _multi_operation(app, master):
    dest = m1.AppAccount(app, SecretKey.pseudo_random_for_testing(41))
    return [master.tx([
        op_create_account(dest.account_id, 10 ** 9),
        op_payment(dest.muxed, 5),
        op_manage_data(b"tail", b"bytes"),
        op_payment(master.muxed, 1)])]


def _fee_bumped(app, master):
    # the master pays the fee of its own inner payment
    inner = master.tx([op_payment(master.muxed, 3)])
    return [_fee_bump(app, inner, master)]


def _soroban_invoke(app, master):
    if app.config.LEDGER_PROTOCOL_VERSION < 20:
        # no contract can exist below protocol 20: the envelope goes
        # into the set as it is and leaves its rows as a failed
        # transaction
        cid = b"\x07" * 32
    else:
        _, cid = sb.deploy(app)
        master.sync_seq()
    ro, rw = sb.invoke_footprints(cid)
    return [sb.soroban_tx(app, master, sb.invoke_op(cid, "increment"),
                          ro, rw)]


TRAFFIC = {"payment": _payment, "multi_operation": _multi_operation,
           "fee_bump": _fee_bumped, "soroban_invoke": _soroban_invoke}


def close_with_frames(app, frames) -> None:
    """Close one ledger holding `frames`, past the queue (which would
    refuse what the protocol cannot apply)."""
    from stellar_core_tpu.herder import make_tx_set_from_transactions
    from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
    from stellar_core_tpu.xdr.ledger import StellarValue
    lm = app.ledger_manager
    lcl = lm.get_last_closed_ledger_header()
    frame, _, excluded = make_tx_set_from_transactions(
        frames, lcl, app.config.network_id())
    assert not excluded
    value = StellarValue(txSetHash=frame.get_contents_hash(),
                         closeTime=lcl.scpValue.closeTime + 5)
    lm.close_ledger(LedgerCloseData(lcl.ledgerSeq + 1, frame, value))
    lm.join_completion()


def tail_app(tmp_path, protocol: int):
    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = protocol
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.ledger_manager.meta_debug_dir = str(tmp_path / "meta-debug")
    app.start()
    return app


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("protocol", [19, 21])
def test_tail_writes_the_plain_encodings_bytes(tmp_path, protocol, traffic):
    app = tail_app(tmp_path, protocol)
    try:
        lm = app.ledger_manager
        assert lm.stores_history_misc and lm.db is not None
        sb.COUNTER_CODE = sb.CODE_BUILDS["scvm"]
        master = m1.master_account(app)
        frames = TRAFFIC[traffic](app, master)
        streamed = []
        lm.meta_stream = streamed.append
        tails = capture_tail(lm)
        already = len(debug_records(lm.meta_debug_dir)) \
            if os.path.isdir(lm.meta_debug_dir) else 0
        close_with_frames(app, frames)
        assert len(tails) == 1 and len(tails[0]["txs"]) == len(frames)
        t = tails[0]
        version = 3 if protocol >= 20 else 2
        assert all(lm_mod._encode_tx_meta(m, t["apply_version"]).disc
                   == version for m in t["tx_metas"])
        assert stored_rows(app.database, t["seq"]) == plain_rows(t)
        want = plain_close_meta(t)
        records = debug_records(lm.meta_debug_dir)
        assert len(records) == already + 1
        assert records[-1] == plain_bytes(want)
        assert len(streamed) == 1 and streamed[0] == want
        assert plain_bytes(streamed[0]) == plain_bytes(want)
    finally:
        app.shutdown()
