"""Signature-dense replay (ISSUE 32): signer resolution at collection,
chunking over one warm bucket, adoption chunk by chunk, and the load
generator's multisig mode.

The reference for authorisation is `benchmark/reference/multisig_model.py`
(plain Python over the pure-Python oracle, nothing of the program). The
device path runs on the CPU at buckets the suite already compiles: the
largest bucket is patched to 16 lanes."""

import gc
import gzip
import hashlib
import os
import shutil
import time
import weakref

import pytest

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.crypto.keys import (SecretKey, clear_verify_cache,
                                          verify_sig_uncached)
from stellar_core_tpu.history import make_tmpdir_archive
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.simulation.load_generator import LoadGenerator
from stellar_core_tpu.tx.signature_checker import (
    PrevalidatedVerifier, collect_signature_tuples, signer_adds)
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State
from stellar_core_tpu.xdr.ledger_entries import Signer
from stellar_core_tpu.xdr.results import (TransactionResultCode,
                                          TransactionResultPair)
from stellar_core_tpu.xdr.types import (PublicKey, SignerKey,
                                        SignerKeyType)

from benchmark.reference import ed25519_oracle, multisig_model as model
from test_fee_bump import bump
from txtest_utils import (TestAccount, TestLedger, op_payment,
                          op_set_options, sign_frame)

XLM = 10_000_000
# two seeds whose public keys end in the same four bytes (found by a
# search over sha256(b"hint-pair-%d")): one hint, two candidate keys
HINT_PAIR = (53547, 91290)


def _key(tag: str) -> SecretKey:
    return SecretKey.from_seed(hashlib.sha256(tag.encode()).digest())


def _signer_op(key_raw: bytes, weight: int, thresholds=None):
    low = med = high = None
    if thresholds is not None:
        low, med, high = thresholds
    return op_set_options(
        inflationDest=None, clearFlags=None, setFlags=None,
        masterWeight=None, lowThreshold=low, medThreshold=med,
        highThreshold=high, homeDomain=None,
        signer=Signer(key=SignerKey(SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                                    key_raw), weight=weight))


def _install(ledger, acct, keys, threshold):
    """One SetOptions transaction: `keys` at weight 1 each, all three
    thresholds `threshold`; applied to the ledger."""
    ops = [_signer_op(k.public_key().raw, 1) for k in keys[:-1]]
    ops.append(_signer_op(keys[-1].public_key().raw, 1,
                          (threshold,) * 3))
    assert acct.apply(ops)


def _tx(acct, ops, signers, ahead=1):
    """The transaction of `acct` that is `ahead` sequence numbers on,
    signed by exactly `signers`."""
    frame = acct.tx(ops, seq=acct.seq + ahead)
    del frame.signatures[:]
    for sk in signers:
        sign_frame(frame, sk)
    return frame


def _flip(frame, i):
    """Flip one bit of signature `i`."""
    ds = frame.signatures[i]
    sig = bytes(ds.signature)
    ds.signature = bytes([sig[0] ^ 1]) + sig[1:]


def _model_accounts(ledger, raws) -> dict:
    out = {}
    for raw in raws:
        acc = ledger.account(PublicKey.ed25519(raw))
        out[raw] = model.Account(
            raw, master_weight=acc.thresholds[0],
            signers=[(bytes(s.key.value), s.weight) for s in acc.signers],
            thresholds=tuple(acc.thresholds[1:4]))
    return out


def _describe(frame) -> dict:
    """`frame` in the model's plain terms."""
    def sigs(f):
        return [(bytes(d.hint), bytes(d.signature)) for d in f.signatures]
    inner = frame.inner if frame.is_fee_bump() else frame
    doc = {"hash": inner.contents_hash(), "signatures": sigs(inner),
           "source": bytes(inner.source_id.value),
           "ops": [(None if op.sourceAccount is None
                    else bytes(op.sourceAccount.account_id().value),
                    model.MEDIUM) for op in inner.tx.operations]}
    if frame.is_fee_bump():
        doc["outer"] = {"hash": frame.contents_hash(),
                        "signatures": sigs(frame),
                        "fee_source": bytes(frame.fee_source_id.value)}
    return doc


def _accounts_of(doc) -> set:
    named = {doc["source"]} | {s for s, _ in doc["ops"] if s}
    if "outer" in doc:
        named.add(doc["outer"]["fee_source"])
    return named


@pytest.fixture(scope="module")
def dense():
    """One in-memory ledger with an account of every class, the
    envelopes to resolve, and the frames given to the resolver."""
    ledger = TestLedger()
    root = ledger.root_account

    def account(tag):
        a = TestAccount(ledger, _key("acct-" + tag))
        assert root.create(a, 1000 * XLM)
        a.sync_seq()
        return a

    single, two, three, limit, payer, other, rotated, shared = (
        account(t) for t in ("single", "2of3", "3of5", "limit20", "sponsor",
                             "other", "rotated", "shared-hint"))
    k2 = [_key(f"2of3-{i}") for i in range(2)]
    k3 = [_key(f"3of5-{i}") for i in range(4)]
    k20 = [_key(f"limit20-{i}") for i in range(19)]
    kr = [_key(f"rotated-{i}") for i in range(3)]
    kh = [SecretKey.from_seed(hashlib.sha256(
        b"hint-pair-%d" % i).digest()) for i in HINT_PAIR]
    assert kh[0].public_key().hint() == kh[1].public_key().hint()
    _install(ledger, two, k2, 2)
    _install(ledger, three, k3, 3)
    _install(ledger, limit, k20, 20)
    _install(ledger, rotated, kr[:2], 2)
    _install(ledger, shared, kh, 1)

    pay = [op_payment(single.muxed, XLM)]
    stranger = _key("stranger")
    good = {
        "single": _tx(single, [op_payment(two.muxed, XLM)], [single.key]),
        "2of3": _tx(two, pay, [k2[1], two.key]),
        "3of5-bumped": bump(_tx(three, pay, [k3[3], three.key, k3[0]]),
                            payer, 400),
        "limit20": _tx(limit, pay, [limit.key] + k20),
        # an operation with a source of its own: both must sign
        "op-source": _tx(single, [op_payment(two.muxed, XLM),
                                  op_payment(single.muxed, XLM,
                                             source=other.muxed)],
                         [single.key, other.key]),
        # the second key of the hint pair: the checker tries the first
        "shared-hint": _tx(shared, pay, [kh[1]]),
    }
    bad = {
        "limit20-one-flipped": _tx(limit, pay, [limit.key] + k20),
        "2of3-one-bad": _tx(two, pay, [two.key, k2[0]]),
        "extra-non-signer": _tx(single, [op_payment(two.muxed, XLM)],
                                [single.key, stranger]),
        "bump-bad-outer": bump(_tx(three, pay, [three.key] + k3[:2]),
                               payer, 400),
    }
    _flip(bad["limit20-one-flipped"], 7)
    _flip(bad["2of3-one-bad"], 1)
    _flip(bad["bump-bad-outer"], 0)
    # the rotation: remove signer 1, add signer 2, then pay with the new
    # set; the resolver sees both frames and the state BEFORE either
    rotation = _tx(rotated, [_signer_op(kr[1].public_key().raw, 0),
                             _signer_op(kr[2].public_key().raw, 1)],
                   [rotated.key, kr[1]])
    after = _tx(rotated, pay, [rotated.key, kr[2]], ahead=2)
    stale = _tx(rotated, pay, [rotated.key, kr[1]], ahead=2)  # rotated out
    frames = list(good.values()) + list(bad.values()) + [rotation, after,
                                                         stale]
    metrics = MetricsRegistry()
    tuples = collect_signature_tuples(frames, None, ledger_state=ledger.root,
                                      metrics=metrics)
    return {"ledger": ledger, "good": good, "bad": bad,
            "rotation": (rotation, after, stale), "frames": frames,
            "tuples": tuples, "metrics": metrics}


def _table(tuples) -> PrevalidatedVerifier:
    pv = PrevalidatedVerifier()
    keys = pv.expect(tuples)
    pv.add_results(tuples, [verify_sig_uncached(*t) for t in tuples], keys)
    return pv


def _check(ledger, frame, pv):
    """(check_valid's answer, its code and inner code by name)."""
    with LedgerTxn(ledger.root) as ltx:
        ok = frame.check_valid(ltx, verify=pv)
    res = frame.result.result
    code = TransactionResultCode(res.disc).name
    inner = None
    if code.startswith("txFEE_BUMP_INNER"):
        inner = TransactionResultCode(res.value.result.result.disc).name
    return ok, code, inner


def _same_verdict(got, want):
    """Authorised or not as the model says, and a refusal with the
    model's codes (an authorised envelope's code is made at apply)."""
    assert got[0] == want[0]
    if not want[0]:
        assert got[1:] == want[1:]


# ------------------------------------------------- (a) signer resolution --

@pytest.mark.parametrize("name", [
    "single", "2of3", "3of5-bumped", "limit20", "op-source", "shared-hint",
    "limit20-one-flipped", "2of3-one-bad", "extra-non-signer",
    "bump-bad-outer"])
def test_resolver_covers_what_the_model_can_ask(dense, name):
    """Every tuple the reference's checks can ask for was made, the
    sequential checker finds each of its questions in a table filled
    from the resolver's tuples, and decides as the model does."""
    frame = {**dense["good"], **dense["bad"]}[name]
    doc = _describe(frame)
    accounts = _model_accounts(dense["ledger"], _accounts_of(doc))
    assert model.candidate_tuples(accounts, doc) <= set(dense["tuples"])
    pv = _table(dense["tuples"])
    ok, code, inner = _check(dense["ledger"], frame, pv)
    assert pv.misses_unknown == 0 and pv.misses_pending == 0
    assert pv.hits > 0
    _same_verdict((ok, code, inner), model.envelope_verdict(accounts, doc))
    assert ok == (name in dense["good"])


def test_resolver_learns_a_rotated_signer_from_the_frames(dense):
    """The new signer is in no ledger state when the tuples are
    collected: only the rotation's SetOptions can tell the resolver."""
    ledger = dense["ledger"]
    rotation, after, stale = dense["rotation"]
    pv = _table(dense["tuples"])
    assert ledger.apply_tx(rotation), rotation.result
    for frame, want in ((after, True), (stale, False)):
        doc = _describe(frame)
        accounts = _model_accounts(ledger, _accounts_of(doc))
        assert model.candidate_tuples(accounts, doc) <= set(dense["tuples"])
        ok, code, inner = _check(ledger, frame, pv)
        _same_verdict((ok, code, inner), model.envelope_verdict(accounts, doc))
        assert ok is want
    assert pv.misses_unknown == 0


def test_resolver_without_state_misses_only_state_signers(dense):
    """Callers that pass no ledger state get the keys the envelopes
    name and the signers the frames' own SetOptions add; what is left
    is a counted unknown miss, verified by the fallback: same answer."""
    tuples = collect_signature_tuples(dense["frames"])
    assert set(tuples) < set(dense["tuples"])
    pv = _table(tuples)
    frame = dense["good"]["limit20"]
    ok, code, _ = _check(dense["ledger"], frame, pv)
    assert ok and code == "txSUCCESS"
    # asked twice: at the source's low and at the payment's medium
    assert pv.misses_unknown == 2 * 19 and pv.hits == 2
    # a single-signer payment resolves from its envelope alone
    pv = _table(tuples)
    assert _check(dense["ledger"], dense["good"]["single"], pv)[0]
    assert pv.misses_unknown == 0


def test_resolver_takes_signers_from_what_is_in_flight(dense):
    """The next checkpoint's tuples are collected while the rotation is
    parsed and not applied: the state still holds the old signer, the
    frames hold no SetOptions, and only the carried adds name the new
    one. The rotated-in key is a hit; the rotated-out key is a stale
    lane (a true fact about three byte strings) that changes neither
    the verdict nor the result code, by the model."""
    ledger = dense["ledger"]
    acct = TestAccount(ledger, _key("acct-in-flight"))
    assert ledger.root_account.create(acct, 1000 * XLM)
    acct.sync_seq()
    ks = [_key(f"in-flight-{i}") for i in range(3)]
    _install(ledger, acct, ks[:2], 2)
    rotation = _tx(acct, [_signer_op(ks[1].public_key().raw, 0),
                          _signer_op(ks[2].public_key().raw, 1)],
                   [acct.key, ks[1]])
    pay = [op_payment(ledger.root_account.muxed, XLM)]
    after = _tx(acct, pay, [acct.key, ks[2]], ahead=2)
    stale = _tx(acct, pay, [acct.key, ks[1]], ahead=2)     # rotated out
    carried = signer_adds([rotation])
    assert carried == {acct.key.public_key().raw: [ks[2].public_key().raw]}
    metrics = MetricsRegistry()
    tuples = collect_signature_tuples([after, stale], None,
                                      ledger_state=ledger.root,
                                      metrics=metrics, carried=carried)
    seen = metrics.to_json()
    # one candidate from the carry alone: the new key under `after`
    assert seen["crypto.collect.carried"]["count"] == 1
    assert seen["crypto.collect.candidates"]["count"] == len(tuples) == 4
    without = collect_signature_tuples([after, stale], None,
                                       ledger_state=ledger.root)
    assert set(tuples) - set(without) == {
        (ks[2].public_key().raw, bytes(after.signatures[1].signature),
         after.contents_hash())}
    assert tuples[:1] + tuples[2:] == without      # the others, in order
    stale_lane = (ks[1].public_key().raw,
                  bytes(stale.signatures[1].signature), stale.contents_hash())
    assert stale_lane in tuples and verify_sig_uncached(*stale_lane)
    assert ledger.apply_tx(rotation), rotation.result
    pv = _table(tuples)
    for frame, want in ((after, True), (stale, False)):
        doc = _describe(frame)
        accounts = _model_accounts(ledger, _accounts_of(doc))
        assert model.candidate_tuples(accounts, doc) <= set(tuples)
        ok, code, inner = _check(ledger, frame, pv)
        _same_verdict((ok, code, inner), model.envelope_verdict(accounts, doc))
        assert ok is want
    assert pv.misses_unknown == 0 and pv.hits > 0
    # the resolver of before: the new key's check is unknown to the table
    pv = _table(without)
    assert _check(ledger, after, pv)[0]
    assert pv.misses_unknown > 0


def test_resolver_counts_signatures_and_candidates(dense):
    seen = dense["metrics"].to_json()
    decorated = sum(len(f.signatures) + (len(f.inner.signatures)
                                         if f.is_fee_bump() else 0)
                    for f in dense["frames"])
    assert seen["crypto.collect.signatures"]["count"] == decorated
    assert seen["crypto.collect.candidates"]["count"] == \
        len(dense["tuples"])
    # the hint pair gives one signature two candidates; the stranger's
    # signature matches no candidate
    assert len(dense["tuples"]) == decorated + 1 - 1


def test_model_matches_the_oracle_on_a_plain_signature():
    sk = hashlib.sha256(b"model").digest()
    pub = ed25519_oracle.secret_to_public(sk)
    msg = hashlib.sha256(b"msg").digest()
    sig = ed25519_oracle.sign(sk, msg)
    acct = {pub: model.Account(pub)}
    doc = {"hash": msg, "signatures": [(pub[-4:], sig)], "source": pub,
           "ops": [(None, model.MEDIUM)]}
    assert model.envelope_verdict(acct, doc) == (True, "txSUCCESS", None)
    doc["signatures"] = [(pub[-4:], sig[:-1] + bytes([sig[-1] ^ 1]))]
    assert model.envelope_verdict(acct, doc) == (False, "txBAD_AUTH", None)


# ------------------------------------------------------------ (b) chunks --

def _tuples(n: int, bad: set) -> tuple:
    sk = _key("chunks")
    pub = sk.public_key().raw
    items, want = [], []
    for i in range(n):
        msg = hashlib.sha256(b"chunk-msg-%d" % i).digest()
        sig = sk.sign(msg)
        if i in bad:
            sig = sig[:5] + bytes([sig[5] ^ 4]) + sig[6:]
        items.append((pub, sig, msg))
        want.append(ed25519_oracle.verify(pub, sig, msg))
    return items, want


@pytest.fixture
def bucket16(monkeypatch):
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)


def test_a_batch_beyond_the_bucket_runs_as_chunks_of_it(bucket16):
    """50 tuples at a largest bucket of 16: four calls of the one shape,
    bit-flipped tuples on both sides of every boundary, verdicts equal
    to the oracle's in order, no more chunks in flight than the constant
    allows."""
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    metrics = MetricsRegistry()
    verifier = TpuBatchVerifier(metrics=metrics)
    items, want = _tuples(50, {15, 16, 31, 32, 47, 48})
    assert want.count(False) == 6
    handle = verifier.verify_tuples_async(items)
    assert isinstance(handle, chunking.ChunkedCollect)
    landed = [(lo, hi, list(v)) for lo, hi, v in handle.chunks()]
    assert [(lo, hi) for lo, hi, _ in landed] == \
        [(0, 16), (16, 32), (32, 48), (48, 50)]
    assert [v for _, _, vs in landed for v in vs] == want
    assert handle() == want                      # the contract as before
    assert handle.max_in_flight == chunking.MAX_CHUNKS_IN_FLIGHT
    seen = metrics.to_json()
    assert seen["crypto.verify.dispatch.chunks"]["count"] == 4
    assert seen["crypto.verify.dispatch.batch"]["count"] == 4
    assert seen["crypto.verify.dispatch.batch"]["sum"] == 50
    # every chunk, the remainder too, is padded into the one bucket
    assert seen["crypto.verify.dispatch.padding"]["sum"] == 4 * 16 - 50
    assert seen["crypto.verify.dispatch.wall"]["count"] == 4
    # a batch that fits the bucket is one call, as before
    plain = verifier.verify_tuples_async(items[:16])
    assert not hasattr(plain, "chunks") and list(plain()) == want[:16]
    assert metrics.to_json()["crypto.verify.dispatch.chunks"]["count"] == 4


def test_a_failed_chunk_falls_back_alone(bucket16):
    """Under the supervisor every chunk is a dispatch of its own: the
    one that fails is answered by the native path, the others by the
    device, and the verdicts stay the oracle's in order."""
    from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier

    class FailsChunkOne(TpuBatchVerifier):
        device_calls = 0

        def verify_tuples_async(self, items, chunk=None):
            if chunk is not None and chunk[0] == 1:
                raise OSError("device lost under chunk 1")
            self.device_calls += 1
            return super().verify_tuples_async(items, chunk)

    inner = FailsChunkOne(metrics=MetricsRegistry())
    sup = BackendSupervisor(inner, dispatch_deadline_ms=60000.0)
    try:
        items, want = _tuples(50, {15, 16, 31, 32, 47, 48})
        handle = sup.verify_tuples_async(items)
        assert isinstance(handle, chunking.ChunkedCollect)
        assert handle() == want
        assert handle.max_in_flight == chunking.MAX_CHUNKS_IN_FLIGHT
        # the chunks share the number the wrapped verifier gave the first
        assert handle.batch == inner.last_batch_id == 1
        status = sup.status()
        assert status["dispatches"] == 4 and inner.device_calls == 3
        assert status["failures"]["transient"] == 1
        assert status["state"] == "CLOSED" and not status["quarantined"]
    finally:
        sup.shutdown()


def test_chunks_yield_none_for_a_chunk_that_raises(bucket16):
    """Without a supervisor a failed chunk is handed out as None and
    the whole-batch call raises; the other chunks still land."""
    def dispatch(part, chunk):
        if chunk[0] == 1:
            raise RuntimeError("boom")
        return lambda: [True] * len(part)
    handle = chunking.ChunkedCollect(None, list(range(40)), dispatch)
    got = [(lo, hi, v) for lo, hi, v in handle.chunks()]
    assert got == [(0, 16, [True] * 16), (16, 32, None),
                   (32, 40, [True] * 8)]
    with pytest.raises(RuntimeError):
        handle()


# ------------------------------------- (c) replay of a tiny dense archive --

ACCOUNTS = 20


def _close_to(app, seq):
    while app.ledger_manager.get_last_closed_ledger_num() < seq:
        app.manual_close()


def _rotate(lg) -> dict:
    """The first `2of3` account of the load generator drops both of its
    extra signers and takes two new keys, and signs with the new set
    from now on. Returns the account's raw key, the keys rotated out and
    those rotated in."""
    from stellar_core_tpu.herder.tx_queue import AddResult
    at = next(i for i, (keys, _, _) in sorted(lg._multisig.items())
              if len(keys) == 3)
    keys, threshold, bumped = lg._multisig[at]
    new = [_key(f"rotated-in-{j}") for j in range(2)]
    ops = [_signer_op(k.public_key().raw, 0) for k in keys[1:]] + \
        [_signer_op(k.public_key().raw, 1) for k in new]
    assert lg._sign_and_submit(lg.accounts[at], ops, signers=keys[:2]) \
        == AddResult.ADD_STATUS_PENDING
    lg._multisig[at] = ([keys[0]] + new, threshold, bumped)
    return {"account": lg.accounts[at].key.public_key().raw,
            "out": [k.public_key().raw for k in keys[1:]],
            "in": [k.public_key().raw for k in new]}


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """A native-verifier publisher: 20 accounts in the four classes,
    their signers installed in ledger 4, multisig payments in ledgers
    5..7 of checkpoint 63 and in ledgers 64..65 of checkpoint 127; in
    ledger 8 one `2of3` account replaces both of its extra signers, so
    each of its payments in checkpoint 127 carries a signature of a key
    that only an operation of checkpoint 63 names."""
    root = str(tmp_path_factory.mktemp("dense") / "archive")
    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = 1000
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 1000
    cfg.HISTORY = {"test": {
        "get": f"cp {root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {root}/{{1}}) && cp {{0}} {root}/{{1}}"}}
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        app.manual_close()                       # the tx-set size upgrade
        lg = LoadGenerator(app, seed=32)
        assert lg.generate_accounts(ACCOUNTS) == ACCOUNTS
        app.manual_close()
        lg.sync_account_seqs()
        assert lg.setup_multisig() == ACCOUNTS - 4     # 4 are `single`
        app.manual_close()
        for _ in range(3):
            assert lg.generate_multisig(ACCOUNTS) == ACCOUNTS
            app.manual_close()
        rotated = _rotate(lg)
        app.manual_close()
        _close_to(app, 63)
        for _ in range(2):
            assert lg.generate_multisig(ACCOUNTS) == ACCOUNTS
            app.manual_close()
        _close_to(app, 127)
        app.ledger_manager.join_completion()
        assert app.history_manager.published_count == 2
        hashes = {int(seq): bytes(h) for seq, h in app.database.query_all(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        yield {"archive": make_tmpdir_archive("test", root), "root": root,
               "passphrase": cfg.NETWORK_PASSPHRASE, "hashes": hashes,
               "failed": lg.failed, "rotated": rotated}
    finally:
        app.shutdown()


class _Recording:
    """Pass-through that keeps every tuple the device was given and
    hands the chunks on."""

    def __init__(self, inner, settle=False, lcl=None):
        self._inner = inner
        self._settle = settle
        self._lcl = lcl
        self.dispatched = []
        self.batches = []       # (tuples, the node's LCL at the dispatch)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def verify_tuples_async(self, items):
        self.dispatched.extend(items)
        self.batches.append((len(items), self._lcl and self._lcl()))
        handle = self._inner.verify_tuples_async(items)
        if not self._settle:
            return handle
        verdicts = handle()     # landed, all of it, before apply looks
        return lambda: verdicts


def _replaying_node(passphrase):
    cfg = get_test_config()
    cfg.NETWORK_PASSPHRASE = passphrase
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    cfg.VERIFY_DISPATCH_DEADLINE_MS = 60000.0
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _catch_up(app, archive, to_ledger, wait_for_device, settle=False,
              each_crank=None):
    verifier = _Recording(
        app.batch_verifier, settle,
        app.ledger_manager.get_last_closed_ledger_num)
    work = CatchupWork(app, archive,
                       CatchupConfiguration(to_ledger=to_ledger),
                       batch_verifier=verifier, batch_grace=60.0)
    app.work_scheduler.schedule(work)
    clock = app.clock
    waited = set()
    while not work.is_done():
        if wait_for_device:
            # hold apply back until every chunk has landed, so that
            # what the table cannot answer is the resolver's fault
            for cp in work.applied_checkpoints:
                if cp._pending_batch is not None and id(cp) not in waited:
                    assert cp._pending_batch[2].wait(300)
                    waited.add(id(cp))
        if each_crank is not None:
            each_crank(work)
        if clock.crank(False) == 0:
            clock.crank(True)
    work.drain(300.0)
    return work, verifier


def _table_counts(work) -> tuple:
    tables = [cp.prevalidated for cp in work.applied_checkpoints]
    return (sum(t.hits for t in tables),
            sum(t.misses_pending for t in tables),
            sum(t.misses_unknown for t in tables))


def test_tiny_dense_archive_replays_through_chunks(archive, bucket16):
    """Checkpoint 63 into a fresh node: the header chain is the native
    publisher's, every tuple went to the device exactly once, every
    chunk was adopted and the table answered every check apply made
    (nothing unknown: the resolver made every tuple from the envelopes
    and the checkpoint's own SetOptions). Then checkpoint 127 over the
    node's own state: its signers come from the ledger, which is true
    only because this is a second `CatchupWork`, started when every
    ledger of checkpoint 63 has applied. One catchup over both
    checkpoints collects 127's tuples while 63 still applies:
    `test_one_catchup_over_both_checkpoints` beside this one."""
    assert archive["failed"] == 0
    app = _replaying_node(archive["passphrase"])
    try:
        work, verifier = _catch_up(app, archive["archive"], 63, True)
        assert work.get_state() == State.WORK_SUCCESS
        lm = app.ledger_manager
        assert lm.get_last_closed_ledger_num() == 63
        assert lm.get_last_closed_ledger_hash() == archive["hashes"][63]
        seen = app.metrics.to_json()
        n = len(verifier.dispatched)
        assert n > 3 * 16 and len(set(verifier.dispatched)) == n
        assert seen["crypto.collect.candidates"]["count"] == n
        assert seen["crypto.verify.dispatch.batch"]["sum"] == n
        chunks = -(-n // 16)
        assert seen["crypto.verify.dispatch.chunks"]["count"] == chunks
        assert seen["catchup.batch.adoptLag"]["count"] == chunks
        hits, pending, unknown = _table_counts(work)
        assert unknown == 0 and pending == 0 and hits > n // 2
        assert seen["crypto.prevalidated.miss.unknown"]["count"] == 0
        assert seen["crypto.prevalidated.hit"]["count"] == hits
        status = app.batch_verifier.status()
        assert status["state"] == "CLOSED" and not status["quarantined"]
        assert not any(status["failures"].values())
        # staged closes under the table prewarm nothing: the device saw
        # the checkpoint's chunks and not one flush of the verify service
        zones = app.perf.report()
        assert zones["ledger.close.applyTx.stage"]["count"] > 0
        assert "crypto.verifyService.flush" not in zones
        assert status["dispatches"] == chunks

        # the second checkpoint: no SetOptions in it, a state with signers
        before = seen["crypto.collect.candidates"]["count"]
        # (its payments are in its first ledger, which applies in the
        # crank that dispatches: the batch is settled at dispatch)
        work, verifier = _catch_up(app, archive["archive"], 0, False, True)
        assert work.get_state() == State.WORK_SUCCESS
        assert lm.get_last_closed_ledger_num() == 127
        assert lm.get_last_closed_ledger_hash() == archive["hashes"][127]
        hits, pending, unknown = _table_counts(work)
        assert unknown == 0 and pending == 0
        # 2 ledgers x (4 + 6*2 + 4*4 + 6*20) signatures
        assert hits >= 2 * 152
        assert app.metrics.to_json()[
            "crypto.collect.candidates"]["count"] - before == \
            len(verifier.dispatched) >= 2 * 152
    finally:
        app.shutdown()


def test_apply_never_waits_for_a_chunk(archive, bucket16):
    """The same replay with apply left to run ahead of the device:
    the chain is the same, and what the table could not answer yet is
    counted as pending, never as unknown."""
    app = _replaying_node(archive["passphrase"])
    try:
        work, verifier = _catch_up(app, archive["archive"], 63, False)
        assert work.get_state() == State.WORK_SUCCESS
        assert app.ledger_manager.get_last_closed_ledger_hash() == \
            archive["hashes"][63]
        hits, pending, unknown = _table_counts(work)
        assert unknown == 0 and hits + pending > 0
        seen = app.metrics.to_json()
        assert seen["crypto.prevalidated.miss"]["count"] == pending
        assert seen["crypto.prevalidated.miss.pending"]["count"] == pending
        assert len(set(verifier.dispatched)) == len(verifier.dispatched)
    finally:
        app.shutdown()


class _KeepsUp:
    """A device that keeps up: chunks of the largest bucket, as many in
    flight as the constant allows, run one after the other at `lane_s`
    seconds a lane of the full bucket, answered with the native
    verifier's verdicts."""

    def __init__(self, lane_s: float):
        self.lane_s = lane_s
        self.last_batch_id = 0
        self.chunks = 0
        self._free_at = 0.0      # when the last run dispatched ends

    def verify_tuples_async(self, items):
        self.last_batch_id += 1

        def dispatch(part, chunk):
            self.chunks += 1
            due = max(time.perf_counter(), self._free_at) \
                + chunking.MAX_BUCKET * self.lane_s
            self._free_at = due

            def collect():
                time.sleep(max(0.0, due - time.perf_counter()))
                return [verify_sig_uncached(p, s, m) for p, s, m in part]
            return collect
        return chunking.ChunkedCollect(self, items, dispatch)


def test_the_cold_checkpoint_is_not_cold_where_the_device_keeps_up(
        archive, monkeypatch):
    """Checkpoint 63 into a fresh node with the grace a node has (50
    ms), a device that runs a 64-lane chunk in 10 ms and apply paced at
    a ledger every 50 ms or slower (152 signatures a payment ledger
    against 320 lanes): the first chunk is in the table before the
    first payment ledger (5) applies, and from the second payment
    ledger on apply outruns nothing."""
    monkeypatch.setattr(chunking, "MAX_BUCKET", 64)
    app = _replaying_node(archive["passphrase"])
    try:
        app.flight_recorder.start()
        device = _KeepsUp(0.010 / 64)
        work = CatchupWork(app, archive["archive"],
                           CatchupConfiguration(to_ledger=63),
                           batch_verifier=device)
        app.work_scheduler.schedule(work)
        lm = app.ledger_manager
        pending_at = {}          # LCL -> pending misses counted so far
        while not work.is_done():
            tables = [cp.prevalidated for cp in work.applied_checkpoints
                      if cp.prevalidated is not None]
            lcl = lm.get_last_closed_ledger_num()
            pending_at[lcl] = sum(t.misses_pending for t in tables)
            if tables and lcl < 8:
                time.sleep(0.050)
            if app.clock.crank(False) == 0:
                app.clock.crank(True)
        work.drain(60.0)
        assert work.get_state() == State.WORK_SUCCESS
        assert lm.get_last_closed_ledger_hash() == archive["hashes"][63]
        hits, pending, unknown = _table_counts(work)
        # ledgers 5..7: (4 + 6*2 + 4*4 + 6*20) signatures each
        assert unknown == 0 and hits + pending >= 3 * 152
        assert device.chunks >= 8
        adopted = [ev["args"] for ev in
                   app.flight_recorder.to_chrome_trace()["traceEvents"]
                   if ev["name"] == "catchup.batch.adopted"]
        assert len(adopted) == device.chunks
        assert [a["lo"] for a in adopted] == \
            [lo for lo, _ in chunking.chunk_bounds(
                sum(a["n"] for a in adopted), 64)]
        # the first chunk before the first payment ledger applies ...
        assert adopted[0]["lo"] == 0 and adopted[0]["seq"] <= 5
        # ... and nothing pending once that ledger has closed
        assert pending_at[5] == pending_at[63] == pending
        assert app.metrics.to_json()[
            "crypto.prevalidated.miss.pending"]["count"] == pending
        assert hits >= 2 * 152
    finally:
        app.shutdown()


# --------------------------- (d) one catchup over more than one checkpoint --

def _chain_of(app) -> dict:
    app.ledger_manager.join_completion()
    return {int(seq): bytes(h) for seq, h in app.database.query_all(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}


def test_one_catchup_over_both_checkpoints(archive, bucket16):
    """Genesis to 127 in one `CatchupWork`: checkpoint 127's tuples are
    collected and dispatched while checkpoint 63 applies, against a
    state that holds none of its signers, and the table still answers
    every check of both checkpoints (apply held until the chunks land):
    the signers come from what checkpoint 63's operations add."""
    app = _replaying_node(archive["passphrase"])
    try:
        work, verifier = _catch_up(app, archive["archive"], 0, True)
        assert work.get_state() == State.WORK_SUCCESS
        chain = _chain_of(app)
        assert set(archive["hashes"]) == set(range(1, 128))
        assert chain == archive["hashes"]
        first, second = work.applied_checkpoints
        for table in (first.prevalidated, second.prevalidated):
            assert table.misses_unknown == 0 and table.misses_pending == 0
            assert table.hits > 0
        # 2 ledgers x (4 + 6*2 + 4*4 + 6*20) signatures
        assert second.prevalidated.hits >= 2 * 152
        seen = app.metrics.to_json()
        assert seen["crypto.prevalidated.miss.unknown"]["count"] == 0
        assert seen["crypto.collect.carried"]["count"] > 0
        n = len(verifier.dispatched)
        assert len(set(verifier.dispatched)) == n
        assert seen["crypto.collect.candidates"]["count"] == n
        # two batches; the second left while the first still applied
        assert len(verifier.batches) == 2
        assert verifier.batches[0][0] + verifier.batches[1][0] == n
        assert verifier.batches[1][1] < 63
        rot = archive["rotated"]
        late = {p for p, _, _ in verifier.dispatched[verifier.batches[0][0]:]}
        assert set(rot["in"]) & late and not set(rot["out"]) & late
        # the new names: once a prefetched checkpoint
        assert app.perf.report()["catchup.prefetch.ahead"]["count"] == 1
        assert seen["catchup.batch.lead"]["count"] == 1
        assert seen["crypto.verify.dispatch.collectWait"]["count"] == \
            app.batch_verifier.status()["dispatches"]
        status = app.batch_verifier.status()
        assert status["state"] == "CLOSED" and not status["quarantined"]
        assert not any(status["failures"].values())
    finally:
        app.shutdown()


def test_range_catchup_with_apply_left_to_run_ahead(archive, bucket16):
    """The same catchup with nothing held back: what the table could
    not answer yet is pending, on either checkpoint, and never unknown."""
    app = _replaying_node(archive["passphrase"])
    try:
        work, verifier = _catch_up(app, archive["archive"], 0, False)
        assert work.get_state() == State.WORK_SUCCESS
        assert app.ledger_manager.get_last_closed_ledger_hash() == \
            archive["hashes"][127]
        hits, pending, unknown = _table_counts(work)
        assert unknown == 0 and hits + pending > 0
        seen = app.metrics.to_json()
        assert seen["crypto.prevalidated.miss.unknown"]["count"] == 0
        assert seen["crypto.prevalidated.miss.pending"]["count"] == pending
        assert seen["crypto.prevalidated.hit"]["count"] == hits
        assert len(set(verifier.dispatched)) == len(verifier.dispatched)
    finally:
        app.shutdown()


def test_a_finished_checkpoint_gives_back_all_but_its_counts(archive,
                                                             bucket16):
    """Inside the running catchup, once checkpoint 63's work has ended:
    its parsed entries and frame sets are collectable, its table holds
    counts and no verdict, and `drain` still settles."""
    app = _replaying_node(archive["passphrase"])
    held = {}

    def look(work):
        if not work.applied_checkpoints:
            return
        first = work.applied_checkpoints[0]
        if "entry" not in held and first._txs_by_seq and first._frame_sets:
            held["entry"] = weakref.ref(first._txs_by_seq[5])
            # (not the newest: the ledger manager's completion worker
            # holds the close it ran last)
            held["frames"] = weakref.ref(first._frame_sets[5])
        if first.is_done() and not work.is_done() and "after" not in held:
            gc.collect()
            held["after"] = {
                "dead": [r() is None for r in (held["entry"],
                                               held["frames"])],
                "counts": (first.prevalidated.hits,
                           first.prevalidated.misses_pending,
                           first.prevalidated.misses_unknown),
                "map": len(first.prevalidated._results),
                "rest": (first._txs_by_seq, first._frame_sets,
                         first._adds, first._pending_batch,
                         first.results_work.results_by_seq),
                "lcl": app.ledger_manager.get_last_closed_ledger_num()}

    try:
        work, _ = _catch_up(app, archive["archive"], 0, True,
                            each_crank=look)
        assert work.get_state() == State.WORK_SUCCESS
        after = held["after"]
        assert 63 <= after["lcl"] < 127
        assert after["dead"] == [True, True]
        assert after["map"] == 0 and after["counts"][0] > 0
        assert after["counts"][1:] == (0, 0)
        assert after["rest"] == (None, {}, {}, None, {})
        # the counts are what the counters took
        assert app.metrics.to_json()["crypto.prevalidated.hit"]["count"] \
            == sum(cp.prevalidated.hits for cp in work.applied_checkpoints)
        work.drain(5.0)
        second = work.applied_checkpoints[1]
        assert second._txs_by_seq is None and not second.prevalidated._results
    finally:
        app.shutdown()


def test_a_corrupt_second_file_fails_its_own_checkpoint(archive, bucket16,
                                                        tmp_path):
    """The transactions file of checkpoint 127 is cut short: its
    prefetch, cranked from checkpoint 63's apply with the carried adds,
    swallows the error, checkpoint 63 replays to its end, and the
    failure is checkpoint 127's own."""
    root = str(tmp_path / "archive")
    shutil.copytree(archive["root"], root)
    path = os.path.join(root, "transactions", "00", "00", "00",
                        "transactions-0000007f.xdr.gz")
    with gzip.open(path, "rb") as f:
        raw = f.read()
    with gzip.open(path, "wb") as f:
        f.write(raw[:len(raw) // 2])
    app = _replaying_node(archive["passphrase"])
    try:
        work, _ = _catch_up(app, make_tmpdir_archive("test", root), 0, True)
        assert work.get_state() == State.WORK_FAILURE
        first, second = work.applied_checkpoints
        assert first.get_state() == State.WORK_SUCCESS
        assert second.get_state() == State.WORK_FAILURE
        assert second._prefetch_failed
        lm = app.ledger_manager
        assert lm.get_last_closed_ledger_num() == 63
        assert lm.get_last_closed_ledger_hash() == archive["hashes"][63]
        assert first.prevalidated.misses_unknown == 0
    finally:
        app.shutdown()


# ----------------------------------------- the load generator's new mode --

def test_standalone_node_closes_a_ledger_of_multisig_load():
    """`generateload` modes multisig_setup and multisig on a live
    standalone node: a ledger of m-of-n, fee-bumped and 20-signature
    payments closes with every transaction a success."""
    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = 1000
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 1000
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        app.manual_close()
        handle = app.command_handler.handle
        out = handle("generateload", {"mode": "create", "accounts": "10"})
        assert out["submitted"] == 10
        app.manual_close()
        assert "exception" in handle("generateload", {"mode": "multisig"})
        out = handle("generateload", {"mode": "multisig_setup"})
        assert out["status"] == "ok" and out["submitted"] == 8
        app.manual_close()
        out = handle("generateload", {"mode": "multisig", "txs": "10"})
        assert out["status"] == "ok" and out["submitted"] == 10
        app.manual_close()
        seq = app.ledger_manager.get_last_closed_ledger_num()
        row = app.database.query_one(
            "SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?", (seq,))
        assert row[0] == 10
        codes = set()
        for (blob,) in app.database.query_all(
                "SELECT txresult FROM txhistory WHERE ledgerseq=?", (seq,)):
            pair = TransactionResultPair.from_bytes(bytes(blob))
            codes.add(TransactionResultCode(pair.result.result.disc).name)
        assert codes == {"txSUCCESS", "txFEE_BUMP_INNER_SUCCESS"}
        lg = app.command_handler._load_generator
        classes = [len(keys) for keys, _, _ in lg._multisig.values()]
        assert sorted(classes) == [1, 1, 3, 3, 3, 5, 5, 20, 20, 20]
        assert lg.failed == 0
    finally:
        app.shutdown()
