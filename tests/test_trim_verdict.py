"""The verdict rides with the frame (ISSUE 42): a transaction that
`check_valid` passed against this LCL is kept by the proposer's trim of
this LCL's set without a second validation, and by nothing else.

What must hold: a verdict is good for one LCL and one sequence number
seen; a `False` answer and the kinds whose answer reads more than the
key are never remembered; and with or without the verdicts a node trims,
bans, closes and archives the same bytes."""

import random

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.tx_queue import AddResult
from stellar_core_tpu.herder.tx_set import (make_tx_set_from_transactions,
                                            trim_invalid)
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.tx.frame import TransactionFrame, make_frame
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger import StellarValue
from stellar_core_tpu.xdr.ledger_entries import Signer
from stellar_core_tpu.xdr.results import TransactionResultCode
from stellar_core_tpu.xdr.transaction import (
    DecoratedSignature, FeeBumpTransaction, FeeBumpTransactionEnvelope,
    LedgerBounds, Memo, MemoType, Preconditions, PreconditionsV2,
    PreconditionType, TimeBounds, Transaction, TransactionEnvelope,
    TransactionV1Envelope, _FeeBumpInnerTx, _TxExt)
from stellar_core_tpu.xdr.types import (EnvelopeType, SignerKey,
                                        SignerKeyType)

import test_standalone_app as m1
from txtest_utils import (op_create_account, op_payment, op_set_options,
                          sign_frame)

CLOSE_TIME = 1_700_000_000
XLM = 10_000_000


# ----------------------------------------------------------------- helpers --

def make_app():
    cfg = get_test_config()
    # close times off the clock: two nodes close the same bytes
    cfg.ARTIFICIALLY_SET_CLOSE_TIME_FOR_TESTING = CLOSE_TIME
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


@pytest.fixture
def app():
    a = make_app()
    yield a
    a.shutdown()


def key(i: int) -> SecretKey:
    return SecretKey.from_seed(bytes([i]) * 32)


def fund(app, n: int, balance: int = 1000 * XLM):
    """n accounts created by the master in one ledger."""
    master = m1.master_account(app)
    accts = [m1.AppAccount(app, key(i + 1)) for i in range(n)]
    admit(app, master.tx([op_create_account(a.account_id, balance)
                          for a in accts]))
    app.manual_close()
    for a in accts:
        a.sync_seq()
    master.sync_seq()
    return master, accts


def build(app, acct, ops, seq=None, fee=None, cond=None, signers=None):
    """A signed v1 frame of `acct`; `seq` None takes its next."""
    if seq is None:
        acct.seq += 1
        seq = acct.seq
    t = Transaction(
        sourceAccount=acct.muxed,
        fee=fee if fee is not None else 100 * max(1, len(ops)),
        seqNum=seq,
        cond=cond or Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE), operations=list(ops), ext=_TxExt(0))
    frame = make_frame(TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=t, signatures=[])), app.config.network_id())
    for sk in signers if signers is not None else [acct.key]:
        sign_frame(frame, sk)
    return frame


def bump(app, inner, payer, fee):
    fb = FeeBumpTransaction(
        feeSource=payer.muxed, fee=fee,
        innerTx=_FeeBumpInnerTx(EnvelopeType.ENVELOPE_TYPE_TX,
                                inner.envelope.value), ext=_TxExt(0))
    env = FeeBumpTransactionEnvelope(tx=fb, signatures=[])
    frame = make_frame(TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP, env),
        app.config.network_id())
    env.signatures = [DecoratedSignature(
        hint=payer.key.public_key().hint(),
        signature=payer.key.sign(frame.contents_hash()))]
    frame.signatures = env.signatures
    return frame


def v2(**kw):
    kw.setdefault("timeBounds", None)
    kw.setdefault("ledgerBounds", None)
    kw.setdefault("minSeqNum", None)
    kw.setdefault("minSeqAge", 0)
    kw.setdefault("minSeqLedgerGap", 0)
    kw.setdefault("extraSigners", [])
    return Preconditions(PreconditionType.PRECOND_V2, PreconditionsV2(**kw))


def admit(app, frame, want=AddResult.ADD_STATUS_PENDING):
    """Through the herder with the frame object itself (the `tx` route
    would parse a copy)."""
    got = app.herder.recv_transaction(frame)
    assert got == want, (got, frame.result)
    return frame


def counts(app):
    m = app.metrics.to_json()
    return (m["herder.trim.verdict.hit"]["count"],
            m["herder.trim.verdict.miss"]["count"])


def lcl_hash(app) -> bytes:
    return app.ledger_manager.get_last_closed_ledger_hash()


def close_beside_the_queue(app, txs=()):
    """Close the next ledger with exactly `txs`, as a set that came from
    the network would: the queue is not trimmed, only aged."""
    lcl = app.ledger_manager.get_last_closed_ledger_header()
    frame, applicable, _ = make_tx_set_from_transactions(
        list(txs), lcl, app.config.network_id())
    value = StellarValue(txSetHash=frame.get_contents_hash(),
                         closeTime=lcl.scpValue.closeTime + 1)
    app.herder.externalize_value(lcl.ledgerSeq + 1, value, applicable)


def applied(app, frame) -> bool:
    return app.database.query_one(
        "SELECT 1 FROM txhistory WHERE txid=?",
        (frame.full_hash(),)) is not None


# ------------------------------------------------- one LCL, one verdict --

def test_admitted_at_the_lcl_is_a_hit_at_its_trigger(app):
    master, accts = fund(app, 3)
    before = counts(app)
    frames = [admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
              for a in accts]
    here = lcl_hash(app)
    assert [f.valid_at for f in frames] == \
        [(here, f.seq_num - 1, 0, 0, True) for f in frames]
    app.manual_close()
    hit, miss = counts(app)
    assert (hit - before[0], miss - before[1]) == (3, 0)
    assert all(applied(app, f) for f in frames)


def test_a_hit_is_not_validated_again(app, monkeypatch):
    master, (a,) = fund(app, 1)
    f = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    calls = []
    real = TransactionFrame.check_valid

    def counting(self, *args, **kw):
        calls.append(self)
        return real(self, *args, **kw)
    monkeypatch.setattr(TransactionFrame, "check_valid", counting)
    kept, dropped = trim_invalid(app.herder.tx_queue.get_transactions(),
                                 app.ledger_manager.root)
    assert kept == [f] and not dropped and not calls


def test_a_close_in_between_makes_it_a_miss(app):
    master, (a,) = fund(app, 1)
    f = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    admitted_at = lcl_hash(app)
    close_beside_the_queue(app)
    assert lcl_hash(app) != admitted_at and f.valid_at[0] == admitted_at
    before = counts(app)
    app.manual_close()
    hit, miss = counts(app)
    assert (hit - before[0], miss - before[1]) == (0, 1)
    assert applied(app, f)


def test_a_miss_that_is_valid_leaves_the_verdict_of_the_new_lcl(app):
    master, (a,) = fund(app, 1)
    f = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    close_beside_the_queue(app)
    root = app.ledger_manager.root
    txs = app.herder.tx_queue.get_transactions()
    assert trim_invalid(txs, root, metrics=app.metrics) == ([f], [])
    assert f.valid_at[0] == lcl_hash(app)
    before = counts(app)
    assert trim_invalid(txs, root, metrics=app.metrics) == ([f], [])
    assert counts(app) == (before[0] + 1, before[1])


def test_the_key_is_the_header_the_txn_stands_on_not_the_node():
    one, two = make_app(), make_app()
    try:
        for node in (one, two):
            fund(node, 1)
        assert lcl_hash(one) == lcl_hash(two)
        a = m1.AppAccount(one, key(1))
        a.sync_seq()
        master = m1.master_account(one)
        # one frame object in two nodes' queues, as a simulation shares it
        f = build(one, a, [op_payment(master.muxed, XLM)])
        admit(one, f)
        admit(two, f)
        one.manual_close()
        assert lcl_hash(one) != lcl_hash(two)
        two.manual_close()
        assert counts(two)[1] == 0 and applied(two, f)
        assert lcl_hash(one) == lcl_hash(two)
    finally:
        one.shutdown()
        two.shutdown()


def test_a_received_set_is_validated_in_full(app, monkeypatch):
    master, (a, b) = fund(app, 2)
    frames = [admit(app, build(app, acct, [op_payment(master.muxed, XLM)]))
              for acct in (a, a, b)]
    lcl = app.ledger_manager.get_last_closed_ledger_header()
    _, applicable, _ = make_tx_set_from_transactions(
        frames, lcl, app.config.network_id())
    # the set's frames are its own, made from the wire; give one of them
    # this LCL's verdict all the same: validation mode does not ask it
    theirs = {t.full_hash(): t for t in applicable.txs}
    assert not any(t is f for f in frames for t in theirs.values())
    assert all(t.valid_at is None for t in theirs.values())
    theirs[frames[2].full_hash()].valid_at = frames[2].valid_at
    calls = []
    real = TransactionFrame.check_valid

    def counting(self, *args, **kw):
        calls.append(self)
        return real(self, *args, **kw)
    monkeypatch.setattr(TransactionFrame, "check_valid", counting)
    before = counts(app)
    assert applicable.check_valid(app.ledger_manager.root)
    assert len(calls) == 3 and counts(app) == before
    # ... and leaves none
    assert [theirs[f.full_hash()].valid_at for f in frames] == \
        [None, None, frames[2].valid_at]


# ------------------------------------------------------- chain followers --

@pytest.mark.parametrize("how", ["dropped", "replaced_by_fee"])
def test_a_follower_whose_predecessor_went(app, how):
    master, (a, payer) = fund(app, 2)
    first = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    follower = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    assert follower.valid_at[1] == first.seq_num
    before = counts(app)
    if how == "dropped":
        # the number the follower saw is no longer what the scratch txn
        # shows: it is validated, and fails as it does today
        app.herder.tx_queue.ban([first])
        app.manual_close()
        hit, miss = counts(app)
        assert (hit - before[0], miss - before[1]) == (0, 1)
        assert follower.result.result.disc == TransactionResultCode.txBAD_SEQ
        assert app.herder.tx_queue.is_banned(follower.full_hash())
        assert not applied(app, follower)
    else:
        # the replacement is another frame, validated on its own (a fee
        # bump carries no verdict); it consumes the same number, so what
        # the follower saw is what it sees
        replacement = admit(app, bump(
            app, build(app, a, [op_payment(master.muxed, 2 * XLM)],
                       seq=first.seq_num), payer, 100 * 10 * 2))
        assert replacement.valid_at is None
        app.manual_close()
        hit, miss = counts(app)
        assert (hit - before[0], miss - before[1]) == (1, 1)
        assert applied(app, replacement) and applied(app, follower)
        assert not applied(app, first)


def test_a_chain_of_hits_consumes_its_numbers_for_a_miss_behind_it(app):
    master, (a,) = fund(app, 1)
    frames = [admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
              for _ in range(2)]
    # the third carries no verdict (time bounds) and must see the second's
    # number in the scratch txn
    frames.append(admit(app, build(
        app, a, [op_payment(master.muxed, XLM)],
        cond=Preconditions(PreconditionType.PRECOND_TIME,
                           TimeBounds(minTime=0, maxTime=0)))))
    before = counts(app)
    app.manual_close()
    hit, miss = counts(app)
    assert (hit - before[0], miss - before[1]) == (2, 1)
    assert all(applied(app, f) for f in frames)


# ------------------------------------- kinds that never carry a verdict --

def _time_bounds(app, a, other, master):
    return build(app, a, [op_payment(master.muxed, XLM)],
                 cond=Preconditions(
                     PreconditionType.PRECOND_TIME,
                     TimeBounds(minTime=0, maxTime=CLOSE_TIME + 10**6)))


def _ledger_bounds(app, a, other, master):
    return build(app, a, [op_payment(master.muxed, XLM)],
                 cond=v2(ledgerBounds=LedgerBounds(minLedger=0,
                                                   maxLedger=1000)))


def _min_seq_age(app, a, other, master):
    return build(app, a, [op_payment(master.muxed, XLM)],
                 cond=v2(minSeqAge=1))


def _min_seq_ledger_gap(app, a, other, master):
    return build(app, a, [op_payment(master.muxed, XLM)],
                 cond=v2(minSeqLedgerGap=1))


def _min_seq_num(app, a, other, master):
    return build(app, a, [op_payment(master.muxed, XLM)], seq=a.seq + 5,
                 cond=v2(minSeqNum=a.seq))


def _extra_signers(app, a, other, master):
    extra = SignerKey(SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                      other.key.public_key().raw)
    return build(app, a, [op_payment(master.muxed, XLM)],
                 cond=v2(extraSigners=[extra]), signers=[a.key, other.key])


def _fee_bump(app, a, other, master):
    return bump(app, build(app, a, [op_payment(master.muxed, XLM)]),
                other, 400)


def _soroban(app, a, other, master):
    from test_sac import contract_addr, sac_create_op
    from test_soroban import soroban_tx
    from stellar_core_tpu.soroban.host import instance_key
    from txtest_utils import native
    body, cid = sac_create_op(app, native())
    return soroban_tx(app, a, body, [], [instance_key(contract_addr(cid))])


@pytest.mark.parametrize("kind", [
    _time_bounds, _ledger_bounds, _min_seq_age, _min_seq_ledger_gap,
    _min_seq_num, _extra_signers, _fee_bump, _soroban],
    ids=lambda f: f.__name__.lstrip("_"))
def test_kind_is_never_stamped(app, kind):
    master, (a, other) = fund(app, 2)
    f = admit(app, kind(app, a, other, master))
    assert f.valid_at is None
    assert f.verdict_key(lcl_hash(app), f.seq_num - 1) is None
    before = counts(app)
    root = app.ledger_manager.root
    # neither admission nor a trim that found it valid leaves one
    assert trim_invalid([f], root, metrics=app.metrics) == ([f], [])
    assert f.valid_at is None
    app.manual_close()
    hit, miss = counts(app)
    assert (hit - before[0], miss - before[1]) == (0, 2)
    assert applied(app, f)


def test_other_arguments_are_another_key(app):
    master, (a,) = fund(app, 1)
    f = build(app, a, [op_payment(master.muxed, XLM)])
    here = lcl_hash(app)
    plain = f.verdict_key(here, a.seq - 1)
    assert plain == f.verdict_key(here, a.seq - 1, 0, 0, True)
    assert len({plain,
                f.verdict_key(here, a.seq - 1, lb_offset=5),
                f.verdict_key(here, a.seq - 1, ub_offset=5),
                f.verdict_key(here, a.seq - 1, charge_fee=False),
                f.verdict_key(here, a.seq),
                f.verdict_key(b"\x00" * 32, a.seq - 1)}) == 6


# ------------------------------------------- what is never remembered --

def test_a_false_verdict_is_never_remembered(app):
    master = m1.master_account(app)
    reserve = app.ledger_manager.get_last_closed_ledger_header().baseReserve
    poor = m1.AppAccount(app, key(9))
    admit(app, master.tx([op_create_account(poor.account_id, 2 * reserve)]))
    app.manual_close()
    poor.sync_seq()
    f = build(app, poor, [op_payment(master.muxed, 1)])
    admit(app, f, want=AddResult.ADD_STATUS_ERROR)
    assert f.result.result.disc == \
        TransactionResultCode.txINSUFFICIENT_BALANCE
    assert f.valid_at is None
    # funded by the next ledger, the same frame is admitted then
    admit(app, master.tx([op_payment(poor.muxed, XLM)]))
    app.manual_close()
    admit(app, f)
    assert f.valid_at[0] == lcl_hash(app)
    before = counts(app)
    app.manual_close()
    assert counts(app) == (before[0] + 1, before[1]) and applied(app, f)


def test_a_trim_that_drops_leaves_no_verdict(app):
    master, (a,) = fund(app, 1)
    f = build(app, a, [op_payment(master.muxed, XLM)], seq=a.seq + 2)
    kept, dropped = trim_invalid([f], app.ledger_manager.root)
    assert (kept, dropped) == ([], [f]) and f.valid_at is None
    assert f.result.result.disc == TransactionResultCode.txBAD_SEQ


def test_a_signer_change_in_ledger_n_voids_the_stamps_of_n_minus_1(app):
    master, (a, s) = fund(app, 2)
    f = admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
    assert f.valid_at[0] == lcl_hash(app)
    # ledger N: the master account's transaction carries an operation of
    # `a` that hands `a` to another key; `a`'s sequence number stays
    rotate = build(
        app, master,
        [op_set_options(source=a.muxed, masterWeight=0, signer=Signer(
            key=SignerKey(SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                          s.key.public_key().raw), weight=1))],
        signers=[master.key, a.key])
    close_beside_the_queue(app, [rotate])
    assert m1.app_account_entry(app, a.account_id).seqNum == f.seq_num - 1
    before = counts(app)
    app.manual_close()
    hit, miss = counts(app)
    assert (hit - before[0], miss - before[1]) == (0, 1)
    assert f.result.result.disc == TransactionResultCode.txBAD_AUTH
    assert app.herder.tx_queue.is_banned(f.full_hash())
    assert not applied(app, f)


# --------------------------- the same trim, with and without the verdicts --

def _queue_all_hits(app, master, accts):
    return [admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
            for a in accts]


def _queue_chains(app, master, accts):
    out = []
    for n, a in enumerate(accts):
        out += [admit(app, build(app, a, [op_payment(master.muxed, XLM)]))
                for _ in range(n % 3 + 1)]
    return out


def _queue_gap_in_a_chain(app, master, accts):
    out = _queue_chains(app, master, accts)
    gone = [f for f in out if f.source_id == accts[2].account_id][:1]
    app.herder.tx_queue.ban(gone)
    return [f for f in out if f not in gone]


def _queue_stale_lcl(app, master, accts):
    out = _queue_chains(app, master, accts[:3])
    close_beside_the_queue(app)
    return out + _queue_all_hits(app, master, accts[3:])


def _queue_kinds(app, master, accts):
    a, b, c, d, e = accts[:5]
    return [admit(app, f) for f in (
        _time_bounds(app, a, b, master),
        build(app, a, [op_payment(master.muxed, XLM)]),
        _min_seq_age(app, b, c, master),
        _extra_signers(app, c, d, master),
        build(app, c, [op_payment(master.muxed, XLM)]),
        _fee_bump(app, d, e, master),
        build(app, e, [op_payment(master.muxed, XLM)]))]


def _queue_state_moved_under_it(app, master, accts):
    out = _queue_chains(app, master, accts)
    a, s = accts[1], accts[0]
    rotate = build(
        app, master,
        [op_set_options(source=a.muxed, masterWeight=0, signer=Signer(
            key=SignerKey(SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                          s.key.public_key().raw), weight=1))],
        signers=[master.key, a.key])
    # and an account's money gone: its queued payments cannot pay the fee
    b = accts[4]
    drain = build(app, master, [op_payment(
        master.muxed, 1000 * XLM - 2 * app.ledger_manager.
        get_last_closed_ledger_header().baseReserve, source=b.muxed)],
        signers=[master.key, b.key])
    close_beside_the_queue(app, [rotate, drain])
    return out


def _trim_of(scenario, monkeypatch, stamped):
    with monkeypatch.context() as mp:
        if not stamped:
            mp.setattr(TransactionFrame, "verdict_key",
                       lambda self, *a, **kw: None)
        app = make_app()
        try:
            master, accts = fund(app, 6)
            scenario(app, master, accts)
            txs = app.herder.tx_queue.get_transactions()
            kept, dropped = trim_invalid(txs, app.ledger_manager.root,
                                         metrics=app.metrics)
            app.herder.tx_queue.ban(dropped)
            app.manual_close()
            return ([t.full_hash() for t in kept],
                    [(t.full_hash(), t.result.to_bytes()) for t in dropped],
                    sorted(h for gen in app.herder.tx_queue._banned
                           for h in gen),
                    lcl_hash(app), counts(app))
        finally:
            app.shutdown()


@pytest.mark.parametrize("scenario", [
    _queue_all_hits, _queue_chains, _queue_gap_in_a_chain, _queue_stale_lcl,
    _queue_kinds, _queue_state_moved_under_it],
    ids=lambda f: f.__name__[len("_queue_"):])
def test_kept_order_and_banned_remainder_are_todays(scenario, monkeypatch):
    with_verdicts = _trim_of(scenario, monkeypatch, True)
    without = _trim_of(scenario, monkeypatch, False)
    assert with_verdicts[:4] == without[:4]
    assert with_verdicts[0], "an empty trim compares nothing"
    # and the mechanism was on in one and off in the other
    assert without[4][0] == 0 and with_verdicts[4][0] > 0
    assert sum(with_verdicts[4]) == sum(without[4])


# ----------------------------------------------------------- differential --

_TABLES = {
    "ledgerheaders": "ledgerseq, ledgerhash, prevhash, closetime, data",
    "txhistory": "ledgerseq, txindex, txid, txbody, txresult, txmeta",
    "txfeehistory": "ledgerseq, txindex, txid, txchanges",
    "txsethistory": "ledgerseq, isgeneralized, txset",
}


def _three_ledgers(seed, monkeypatch, stamped):
    with monkeypatch.context() as mp:
        if not stamped:
            mp.setattr(TransactionFrame, "verdict_key",
                       lambda self, *a, **kw: None)
        app = make_app()
        try:
            master, accts = fund(app, 12)
            rng = random.Random(seed)
            for _ in range(3):
                for a in rng.sample(accts, 9):
                    for _ in range(rng.choice((1, 1, 2))):
                        to = rng.choice(accts)
                        admit(app, build(app, a, [op_payment(
                            to.muxed, rng.randrange(1, 50) * XLM)]))
                app.manual_close()
            app.herder.join_completion()
            rows = {table: [tuple(bytes(c) if isinstance(
                        c, (bytes, memoryview)) else c for c in r)
                    for r in app.database.query_all(
                        f"SELECT {cols} FROM {table} ORDER BY 1, 2")]
                    for table, cols in _TABLES.items()}
            return rows, counts(app)
        finally:
            app.shutdown()


def test_three_seeded_ledgers_are_byte_identical_without_the_stamp(
        monkeypatch):
    rows, (hit, miss) = _three_ledgers(42, monkeypatch, True)
    plain, (plain_hit, plain_miss) = _three_ledgers(42, monkeypatch, False)
    assert hit > 30 and miss == 0
    assert plain_hit == 0 and plain_miss == hit
    assert len(rows["ledgerheaders"]) == 5 and len(rows["txhistory"]) == hit
    for table in _TABLES:
        assert rows[table] == plain[table], table
