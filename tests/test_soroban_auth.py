"""Relayed Stellar-Asset-Contract transfers (ISSUE 41): the device's
verdicts on auth-entry signatures reach the Soroban host through apply
(`TransactionFrame.apply(verify=...)` -> `ApplyContext.verify` ->
`SorobanHost.verify`), the host counts what answered them, and the
results are those of a dictionary model written from the CAPs
(`benchmark/reference/soroban_auth_model.py`, over the pure-Python
oracle, nothing of the program).

The traffic is the benchmark's builder at a small size
(`benchmark/generators/soroban_transfers.py`); the device path runs on
the CPU with the largest bucket patched to 16 lanes."""

import pytest

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.crypto.keys import clear_verify_cache
from stellar_core_tpu.history import make_tmpdir_archive
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.tx.signature_checker import (PrevalidatedVerifier,
                                                   collect_signature_tuples)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State
from stellar_core_tpu.xdr.transaction import (
    DecoratedSignature, FeeBumpTransaction, FeeBumpTransactionEnvelope,
    TransactionEnvelope, _FeeBumpInnerTx, _TxExt)
from stellar_core_tpu.xdr.types import EnvelopeType

from benchmark.generators.payments import submit
from benchmark.generators.soroban_replay import (AUTH_FAILURE, SOUND,
                                                 result_of)
from benchmark.generators.soroban_transfers import (KINDS, SorobanTraffic,
                                                    nonce_key)
from benchmark.harness import node
from benchmark.reference import ed25519_oracle, soroban_auth_model
from benchmark.reference.soroban_auth_model import (FAILED, SUCCESS,
                                                    SorobanAuthModel)

DEP = {"accounts": 12, "relayed_per_ledger": 9, "adversarial_per_kind": 1,
       "signature_expiration_ahead": 100, "starting_balance": 10**11,
       "amounts": [10000, 20000, 50000, 70000]}
SOUND_DEP = dict(DEP, adversarial_per_kind=0)


def _config(history_root=None):
    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = 1000
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 1000
    cfg.TESTING_SOROBAN_HIGH_LIMIT_OVERRIDE = True
    if history_root is not None:
        cfg.HISTORY = {"test": {
            "get": f"cp {history_root}/{{0}} {{1}}",
            "put": f"mkdir -p $(dirname {history_root}/{{1}}) && "
                   f"cp {{0}} {history_root}/{{1}}"}}
    return cfg


class Net:
    """A standalone node at ledger 4 (upgrade, accounts, the contract)
    with the builder's traffic and the model beside it."""

    def __init__(self, seed=41, dep=DEP, cfg=None):
        clear_verify_cache()
        self.app = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg or _config())
        self.app.start()
        self.nid = self.app.config.network_id()
        self.traffic = SorobanTraffic(seed, self.nid, dep)
        self.model = SorobanAuthModel(self.nid, self.traffic.contract_id)
        self.setup_frames = self.traffic.fund(self.app, self.model)
        self.ledgers = {}          # seq -> [(frame, Transfer, kind)]

    @property
    def lm(self):
        return self.app.ledger_manager

    def next_ledger(self):
        return self.traffic.next_ledger(
            self.lm.get_last_closed_ledger_num() + 1)

    def close(self, ledger, verify=None):
        """Admit `ledger`'s frames and close; with `verify`, the close
        is handed that verifier as a replayed close is."""
        submit(self.app, [f for f, _, _ in ledger])
        seq = self.lm.get_last_closed_ledger_num() + 1
        self.ledgers[seq] = ledger
        if verify is None:
            self.app.manual_close()
            return seq
        close = self.lm.close_ledger
        self.lm.close_ledger = lambda lcd, **kw: close(lcd, verify=verify)
        try:
            self.app.manual_close()
        finally:
            self.lm.close_ledger = close
        return seq

    def results(self, seq):
        """[(frame, Transfer, kind, archived result)] of ledger `seq`
        in the order it applied."""
        by_id = {f.full_hash(): (f, tr, kind)
                 for f, tr, kind in self.ledgers[seq]}
        rows = self.app.database.query_all(
            "SELECT txid, txresult FROM txhistory WHERE ledgerseq = ? "
            "ORDER BY txindex", (seq,))
        assert len(rows) == len(by_id)
        return [by_id[bytes(txid)] + (result_of(bytes(pair)),)
                for txid, pair in rows]

    def run_model(self, seq):
        """Advance the model over ledger `seq` in apply order; returns
        how many archived results differ from the model's."""
        differ = 0
        for _, tr, kind, got in self.results(seq):
            verdict, why = self.model.apply(seq, tr)
            if got != (verdict, SOUND if why is None else AUTH_FAILURE):
                differ += 1
        return differ

    def counter(self, name):
        return self.app.metrics.to_json().get(name, {}).get("count", 0)

    def nonces(self):
        """The (address, nonce) pairs of the node's nonce entries."""
        pair_of = {nonce_key(tr.frm, tr.nonce).to_bytes(): (tr.frm, tr.nonce)
                   for ledger in self.ledgers.values()
                   for _, tr, _ in ledger if tr.credential == "address"}
        keys = self.lm.root.contract_entry_keys()
        return [pair_of[k] for k in keys if k in pair_of]

    def accounts(self):
        return node.account_states(
            self.app, [a.raw for a in self.traffic.accounts])

    def shutdown(self):
        self.app.shutdown()


@pytest.fixture
def net():
    n = Net()
    yield n
    n.shutdown()


def _table_for(frames, nid, lie_about=None):
    """A checkpoint's table as catchup fills it: every tuple of
    `frames`, envelope and auth alike, with the oracle's verdict (and
    `False` for the tuple `lie_about`)."""
    tuples = collect_signature_tuples(frames, nid)
    table = PrevalidatedVerifier()
    table.add_results(tuples, [
        False if t == lie_about else ed25519_oracle.verify(*t)
        for t in tuples], table.expect(tuples))
    return table, tuples


def _auth_tuple(nid, contract_id, tr):
    return (tr.signer, tr.signature,
            soroban_auth_model.auth_payload(nid, contract_id, tr))


# ------------------------------------------------------------ the seam --

def test_a_table_handed_to_a_close_answers_the_hosts_auth_checks():
    """Through apply, not by hand: the table of a close of relayed
    transfers answers every envelope check and every auth check, and
    the host counts the latter. (On a tree whose `_apply_operations`
    leaves `ApplyContext.verify` unset the host verifies natively: the
    table's hits lack the auth signatures and the counter reads 0.)"""
    net = Net(dep=SOUND_DEP)
    try:
        ledger = net.next_ledger()
        frames = [f for f, _, _ in ledger]
        table, tuples = _table_for(frames, net.nid)
        auth = sum(1 for _, tr, _ in ledger if tr.credential == "address")
        assert auth == 9 and len(tuples) == len(frames) + auth
        seq = net.close(ledger, verify=table)
        assert all(got == (SUCCESS, SOUND)
                   for _, _, _, got in net.results(seq))
        # apply checks an envelope's one signature twice (the
        # transaction's threshold, then its operation's) and an auth
        # signature once
        assert table.misses == 0
        assert table.hits == 2 * len(frames) + auth
        assert net.counter("soroban.auth.verify.prevalidated") == auth
        assert net.counter("soroban.auth.verify.fallback") == 0
        assert net.counter("soroban.auth.entries.address") == auth
        assert net.counter("soroban.auth.entries.source") == 3
        assert net.counter("soroban.auth.failed") == 0
        zones = net.app.perf.report()
        assert zones["soroban.invoke"]["count"] == 1 + len(frames)
        assert zones["soroban.auth"]["count"] == auth
        assert 0 < zones["soroban.auth"]["total_ms"] \
            <= zones["soroban.invoke"]["total_ms"] \
            <= zones["ledger.close.applyTx"]["total_ms"]
    finally:
        net.shutdown()


def test_no_table_same_results_and_the_fallback_counts_them(net):
    ledger = net.next_ledger()
    seq = net.close(ledger)
    assert net.run_model(seq) == 0
    asked = net.model.verified
    assert asked == 9 - 2           # expired and wrong signer ask none
    assert net.counter("soroban.auth.verify.fallback") == asked
    assert net.counter("soroban.auth.verify.prevalidated") == 0
    assert net.counter("soroban.auth.failed") == len(KINDS)
    assert net.model.differences(net.accounts()) == 0


def test_a_false_verdict_fails_exactly_that_transfer_and_the_chain_holds():
    """Two nodes are given the same ledger, one auth signature of it
    bit-flipped (the builder's fault: a flipped signature is part of
    the transaction's hash): the native one verifies it itself, the
    other asks a table that says `False` for that tuple, as the device
    would. Same failed transfers, same fee, same chain."""
    native, tabled = Net(), Net()
    try:
        a, b = native.next_ledger(), tabled.next_ledger()
        assert [f.full_hash() for f, _, _ in a] == \
            [f.full_hash() for f, _, _ in b]
        table, tuples = _table_for([f for f, _, _ in b], tabled.nid)
        flipped = next(tr for _, tr, kind in b if kind == "bad_signature")
        bad = _auth_tuple(tabled.nid, tabled.traffic.contract_id, flipped)
        assert bad in tuples and table(*bad) is False
        asked = table.hits
        sa, sb = native.close(a), tabled.close(b, verify=table)
        assert sa == sb
        assert native.lm.get_last_closed_ledger_hash() == \
            tabled.lm.get_last_closed_ledger_hash()
        for n, seq in ((native, sa), (tabled, sb)):
            failed = [(kind, got) for _, _, kind, got in n.results(seq)
                      if got[0] == FAILED]
            assert sorted(k for k, _ in failed) == sorted(KINDS)
            assert all(got == (FAILED, AUTH_FAILURE) for _, got in failed)
        # nothing moved and the fee is charged: the model's accounts
        assert tabled.run_model(sb) == 0
        assert tabled.model.differences(tabled.accounts()) == 0
        assert tabled.accounts() == native.accounts()
        assert table.misses == 0 and table.hits > asked
        assert tabled.counter("soroban.auth.verify.fallback") == 0
        assert tabled.counter("soroban.auth.verify.prevalidated") == \
            native.counter("soroban.auth.verify.fallback") == 9 - 2
    finally:
        native.shutdown()
        tabled.shutdown()


def test_a_lying_table_fails_the_transfer_it_lies_about():
    """A table that says `False` for one sound auth tuple fails exactly
    that transfer with the host's auth error, and charges its fee."""
    net = Net(dep=SOUND_DEP)
    try:
        ledger = net.next_ledger()
        victim = next(tr for _, tr, _ in ledger
                      if tr.credential == "address")
        lie = _auth_tuple(net.nid, net.traffic.contract_id, victim)
        table, _ = _table_for([f for f, _, _ in ledger], net.nid,
                              lie_about=lie)
        seq = net.close(ledger, verify=table)
        failed = [tr for _, tr, _, got in net.results(seq)
                  if got != (SUCCESS, SOUND)]
        assert failed == [victim]
        got = next(g for _, tr, _, g in net.results(seq) if tr == victim)
        assert got == (FAILED, AUTH_FAILURE)
        assert net.counter("soroban.auth.failed") == 1
        balance, seq_num = net.accounts()[victim.source]
        paid = victim.inclusion_fee + victim.resource_fee
        received = sum(tr.amount for _, tr, _ in ledger
                       if tr.to == victim.source and tr != victim)
        sent = sum(tr.amount for _, tr, _ in ledger
                   if tr.frm == victim.source and tr != victim)
        assert balance == DEP["starting_balance"] - paid + received - sent
    finally:
        net.shutdown()


# ------------------------------------------- the model, fault by fault --

@pytest.mark.parametrize("kind,why", [
    ("bad_signature", "bad signature"),
    ("nonce_reuse", "nonce already used"),
    ("expired", "signature expired"),
    ("wrong_signer", "signer is not the address"),
    ("source_entry", "no authorization")])
def test_each_refusal_is_the_models(net, kind, why):
    """A bit-flipped signature, a reused nonce, an expired entry, a
    signature by a key that is not the address, and a relayed transfer
    that carries only a source-account entry: each fails in the program
    with the host's auth error where the model says it fails, and for
    the model's reason."""
    first = net.next_ledger()
    if kind == "source_entry":
        # a relayed transfer whose entry has source-account credentials
        t = net.traffic
        src, frm, dst = t.accounts[0], t.accounts[1], t.accounts[2]
        first = [e for e in first if e[1].source != src.raw]
        src.seq = node.account_seq(net.app, src.raw) + 1
        first.append(t._transfer(net.lm.get_last_closed_ledger_num() + 1,
                                 src, frm, dst, 10000, None, kind))
    seq = net.close(first)
    rows = net.results(seq)
    model_says = {}
    for _, tr, k, got in rows:
        verdict, reason = net.model.apply(seq, tr)
        assert got == (verdict, SOUND if reason is None else AUTH_FAILURE)
        model_says[k] = (verdict, reason)
    if kind == "nonce_reuse":
        # in the first ledger the reused pair is this ledger's own:
        # whichever of the two applied second is the one refused
        assert sum(1 for _, _, _, got in rows if got[0] == FAILED) == \
            len(KINDS)
        second = net.next_ledger()
        seq = net.close(second)
        for _, tr, k, got in net.results(seq):
            verdict, reason = net.model.apply(seq, tr)
            assert got == (verdict,
                           SOUND if reason is None else AUTH_FAILURE)
            if k == kind:
                assert (verdict, reason) == (FAILED, why)
    else:
        assert model_says[kind] == (FAILED, why)
    assert net.model.differences(net.accounts()) == 0
    assert net.model.nonce_differences(net.nonces()) == 0


def test_the_models_payload_is_the_programs(net):
    """What `from` signs: the model's bytes (the preimage's XDR written
    out by hand) and the program's (`soroban_auth_payload`, which the
    builder signs and the collector hands the device) are one 32-byte
    hash, and the oracle accepts the signature over it."""
    from stellar_core_tpu.soroban.host import soroban_auth_payload
    ledger = net.next_ledger()
    tuples = set(collect_signature_tuples([f for f, _, _ in ledger],
                                          net.nid))
    kind_of = {tr.signature: kind for _, tr, kind in ledger}
    seen = 0
    for f, tr, _ in ledger:
        if tr.credential != "address":
            continue
        entry = f.tx.operations[0].body.value.auth[0]
        ac = entry.credentials.value
        mine = soroban_auth_model.auth_payload(
            net.nid, net.traffic.contract_id, tr)
        assert len(mine) == 32
        assert mine == soroban_auth_payload(
            net.nid, ac.nonce, ac.signatureExpirationLedger,
            entry.rootInvocation)
        assert (tr.signer, tr.signature, mine) in tuples
        assert ed25519_oracle.verify(tr.signer, tr.signature, mine) == \
            (kind_of[tr.signature] != "bad_signature")
        seen += 1
    assert seen == 9


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 7410011001])
def test_model_against_program_on_seeded_traffic(seed):
    """Balances, sequence numbers, nonce entries and result codes over
    four ledgers of seeded transfers, faults included."""
    net = Net(seed=seed)
    try:
        for _ in range(4):
            seq = net.close(net.next_ledger())
            assert net.run_model(seq) == 0
        assert net.model.applied == 4 * DEP["accounts"]
        assert net.model.differences(net.accounts()) == 0
        pairs = net.nonces()
        assert len(pairs) == len(set(pairs)) == len(net.model.used)
        assert net.model.nonce_differences(pairs) == 0
        assert net.counter("soroban.auth.failed") == 4 * len(KINDS)
        # every signature the model asked a verdict for, the host did
        assert net.counter("soroban.auth.verify.fallback") == \
            net.model.verified
    finally:
        net.shutdown()


# ------------------------------------------------------------ fee bump --

def test_a_fee_bumped_relayed_transfer_hands_the_table_to_the_inner_host():
    net = Net(dep=SOUND_DEP)
    try:
        ledger = net.next_ledger()
        at = next(i for i, (_, tr, _) in enumerate(ledger)
                  if tr.credential == "address")
        inner, tr, kind = ledger[at]
        # a sponsor that is the source of nothing else in this ledger
        payer = net.traffic.root
        fb = FeeBumpTransaction(
            feeSource=payer.muxed, fee=2 * inner.tx.fee,
            innerTx=_FeeBumpInnerTx(EnvelopeType.ENVELOPE_TYPE_TX,
                                    inner.envelope.value), ext=_TxExt(0))
        env = FeeBumpTransactionEnvelope(tx=fb, signatures=[])
        bumped = make_frame(TransactionEnvelope(
            EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP, env), net.nid)
        env.signatures = [DecoratedSignature(
            hint=payer.hint, signature=payer.key.sign(
                bumped.contents_hash()))]
        bumped.signatures = env.signatures
        ledger = [(bumped, tr, kind)]
        table, tuples = _table_for([bumped], net.nid)
        assert len(tuples) == 3      # the bump's, the inner's, the auth
        seq = net.close(ledger, verify=table)
        (_, _, _, got), = net.results(seq)
        assert got[0] == FAILED or got == (SUCCESS, SOUND)
        assert table.misses == 0 and table.hits >= 3
        assert net.counter("soroban.auth.verify.prevalidated") == 1
        assert net.counter("soroban.auth.verify.fallback") == 0
    finally:
        net.shutdown()


# ------------------------------------------------------------- catchup --

@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """A native publisher: checkpoint 63 with three ledgers of twelve
    transfers (nine relayed, one fault of each kind) in ledgers 5..7."""
    root = str(tmp_path_factory.mktemp("soroban") / "archive")
    net = Net(cfg=_config(root))
    try:
        for _ in range(3):
            net.close(net.next_ledger())
        while net.lm.get_last_closed_ledger_num() < 63:
            net.app.manual_close()
        net.lm.join_completion()
        assert net.app.history_manager.published_count == 1
        hashes = {int(seq): bytes(h) for seq, h in
                  net.app.database.query_all(
                      "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        differ = sum(net.run_model(seq) for seq in sorted(net.ledgers))
        yield {"archive": make_tmpdir_archive("test", root),
               "hashes": hashes, "differ": differ, "model": net.model,
               "auth": sum(1 for ledger in net.ledgers.values()
                           for _, tr, _ in ledger
                           if tr.credential == "address"),
               "frames": len(net.setup_frames) + 3 * DEP["accounts"],
               "accounts": [a.raw for a in net.traffic.accounts],
               "pair_of": {
                   nonce_key(tr.frm, tr.nonce).to_bytes():
                   (tr.frm, tr.nonce)
                   for ledger in net.ledgers.values()
                   for _, tr, _ in ledger if tr.credential == "address"}}
    finally:
        net.shutdown()


def test_a_small_catchup_verifies_both_kinds_of_signature_on_the_device(
        archive, monkeypatch):
    """Checkpoint 63 into a fresh node with the device backend (CPU
    JAX, 16 lanes a chunk, so the batch is several chunks and a
    remainder): the publisher's chain, every auth signature a tuple of
    the batch, every check of apply answered by the table, no native
    verify during apply, and the model's accounts and nonces."""
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)
    assert archive["differ"] == 0
    cfg = _config()
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    cfg.VERIFY_DISPATCH_DEADLINE_MS = 60000.0
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        work = CatchupWork(app, archive["archive"],
                           CatchupConfiguration(to_ledger=0),
                           batch_grace=60.0)
        app.work_scheduler.schedule(work)
        waited = set()
        while not work.is_done():
            # hold apply back until every chunk has landed, so that what
            # the table cannot answer is the collector's fault
            for cp in work.applied_checkpoints:
                if cp._pending_batch is not None and id(cp) not in waited:
                    assert cp._pending_batch[2].wait(300)
                    waited.add(id(cp))
            if app.clock.crank(False) == 0:
                app.clock.crank(True)
        work.drain(300.0)
        assert work.get_state() == State.WORK_SUCCESS
        lm = app.ledger_manager
        assert lm.get_last_closed_ledger_num() == 63
        on_disk = {int(seq): bytes(h) for seq, h in app.database.query_all(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        assert {s: on_disk.get(s) for s in archive["hashes"]} == \
            archive["hashes"]
        seen = app.metrics.to_json()
        n = archive["frames"] + archive["auth"]
        assert seen["crypto.collect.auth"]["count"] == archive["auth"] == 27
        assert seen["crypto.collect.candidates"]["count"] == n
        assert seen["crypto.verify.dispatch.batch"]["sum"] == n
        assert n > 3 * 16 and n % 16
        assert seen["crypto.verify.dispatch.chunks"]["count"] == -(-n // 16)
        assert seen["crypto.prevalidated.miss.unknown"]["count"] == 0
        assert seen["crypto.prevalidated.miss.pending"]["count"] == 0
        model = archive["model"]
        assert seen["soroban.auth.verify.prevalidated"]["count"] == \
            model.verified
        assert seen["soroban.auth.verify.fallback"]["count"] == 0
        assert seen["soroban.auth.failed"]["count"] == 3 * len(KINDS)
        assert "crypto.verify.native" not in app.perf.report()
        assert model.differences(
            node.account_states(app, archive["accounts"])) == 0
        pairs = [archive["pair_of"][k] for k in
                 lm.root.contract_entry_keys() if k in archive["pair_of"]]
        assert model.nonce_differences(pairs) == 0
    finally:
        app.shutdown()


# ------------------------------------------------------------- loadgen --

def test_generateload_sac_auth_reaches_the_relayed_form():
    """`generateload mode=sac_setup` and `mode=sac_auth` through the
    command handler: an operator makes this traffic without the
    benchmark, relayed transfers apply and the host counts their
    entries by credential type."""
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _config())
    app.start()
    try:
        from stellar_core_tpu.main.command_handler import CommandHandler
        handler = CommandHandler(app)
        call = handler.handle
        app.manual_close()
        assert call("generateload", {"mode": "sac_auth"}).get("exception")
        assert call("generateload", {"mode": "create", "accounts": "10"}
                    )["submitted"] == 10
        app.manual_close()
        assert "sac_setup" in call("generateload",
                                   {"mode": "sac_auth"})["exception"]
        assert len(call("generateload", {"mode": "sac_setup"})["contract"]) \
            == 64
        app.manual_close()
        done = call("generateload", {"mode": "sac_auth", "txs": "10",
                                     "relayed": "0.8"})
        assert done == {"status": "ok", "mode": "sac_auth", "submitted": 10}
        app.manual_close()
        seen = app.metrics.to_json()
        assert seen["soroban.auth.entries.address"]["count"] == 8
        assert seen["soroban.auth.entries.source"]["count"] == 2
        assert seen["soroban.auth.verify.fallback"]["count"] == 8
        assert seen["soroban.auth.failed"]["count"] == 0
        rows = app.database.query_all(
            "SELECT txresult FROM txhistory WHERE ledgerseq = ?",
            (app.ledger_manager.get_last_closed_ledger_num(),))
        assert [result_of(bytes(r)) for r, in rows] == \
            [(SUCCESS, SOUND)] * 10
        assert handler._load_generator.failed == 0
    finally:
        app.shutdown()
