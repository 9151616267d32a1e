"""What a close reads once for its contract transactions (ISSUE 43):
the Soroban network configuration is built at the close's first Soroban
operation and dropped before its upgrades, and a footprint's TTL keys
(and the CONFIG_SETTING keys) ride the close's one prefetch, which a
full root cache no longer turns away. Same answers as a configuration
built for every operation and a point SELECT for every TTL key: two
nodes are held to that byte for byte.

The traffic is `tests/test_soroban_auth.py`'s: the benchmark's builder
at a small size, relayed and self-signed transfers and all four
adversarial kinds."""

import base64
from contextlib import contextmanager

import pytest

from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.db.database import Database
from stellar_core_tpu.herder.upgrades import (ConfigUpgradeSetFrame,
                                              UpgradeParameters)
from stellar_core_tpu.ledger import ledger_txn
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn, LedgerTxnRoot
from stellar_core_tpu.main import Application
from stellar_core_tpu.soroban import network_config as nc
from stellar_core_tpu.soroban import ops as soroban_ops
from stellar_core_tpu.soroban.host import instance_key, ttl_key_for
from stellar_core_tpu.soroban.network_config import (CONFIG_SETTING_KEYS,
                                                     SorobanNetworkConfig)
from stellar_core_tpu.tx import footprint
from stellar_core_tpu.tx.footprint import extract_footprint
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.contract import (ConfigSettingEntry,
                                           ConfigSettingID,
                                           ConfigUpgradeSet,
                                           ConfigUpgradeSetKey,
                                           ContractDataDurability,
                                           ContractDataEntry,
                                           InvokeHostFunctionResultCode,
                                           SCAddress, SCAddressType, SCVal,
                                           SCValType, TTLEntry)
from stellar_core_tpu.xdr.ledger_entries import (LedgerEntry,
                                                 LedgerEntryType, LedgerKey,
                                                 _LedgerEntryData,
                                                 _LedgerEntryExt)
from stellar_core_tpu.xdr.types import ExtensionPoint, PublicKey

from benchmark.generators.payments import PaymentTraffic, submit
from benchmark.generators.soroban_replay import AUTH_FAILURE, SOUND
from benchmark.generators.soroban_transfers import nonce_key
from benchmark.reference.ledger_model import LedgerModel
from benchmark.reference.soroban_auth_model import FAILED, SUCCESS

from test_soroban_auth import DEP, KINDS, Net, _config

RELAYED = DEP["relayed_per_ledger"]


@contextmanager
def per_transaction_reads():
    """The tree before ISSUE 43, for the node closed inside: a
    configuration built for every operation, and no TTL key in a
    footprint (so every first touch of one is the root's own read)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(soroban_ops, "_load_config", SorobanNetworkConfig)
        mp.setattr(footprint, "_HAS_TTL", ())
        yield


def _bucketlist_config():
    cfg = _config()
    cfg.EXPERIMENTAL_BUCKETLIST_DB = True
    return cfg


def _small_cache_config():
    """A root cache the first transfer ledger already overflows."""
    cfg = _config()
    cfg.ENTRY_CACHE_SIZE = 8
    return cfg


CONFIGS = {"sql": _config, "bucketlist": _bucketlist_config,
           "full-cache": _small_cache_config}


def _state(net, seqs):
    """What two nodes that closed the same ledgers must agree on."""
    header = net.lm.get_last_closed_ledger_header()
    rows = net.app.database.query_all(
        "SELECT ledgerseq, txindex, txid, txresult, txmeta FROM txhistory "
        "WHERE ledgerseq >= ? ORDER BY ledgerseq, txindex", (min(seqs),))
    return {"hash": net.lm.get_last_closed_ledger_hash(),
            "txSetResultHash": bytes(header.txSetResultHash),
            "history": [tuple(bytes(c) if isinstance(c, (bytes, memoryview))
                              else c for c in row) for row in rows],
            "nonces": sorted(net.nonces()),
            "accounts": net.accounts()}


def _close_ledgers(net, n):
    net.lm.defer_completion = False       # txhistory is read right after
    return [net.close(net.next_ledger()) for _ in range(n)]


@contextmanager
def _reads_inside_apply(net):
    """[point reads of the root inside `_apply_transactions`] of the
    closes made inside."""
    lm, root, seen = net.lm, net.lm.root, []
    apply = lm._apply_transactions

    def counted(*a, **kw):
        before = root.point_reads
        try:
            return apply(*a, **kw)
        finally:
            seen.append(root.point_reads - before)
    lm._apply_transactions = counted
    try:
        yield seen
    finally:
        lm._apply_transactions = apply


# ------------------------------------------------- (a) the same answers --

@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_shared_reads_give_the_ledger_of_per_transaction_reads(which):
    """Two nodes, the same two ledgers of relayed, self-signed and all
    four adversarial transfers (the second reuses a nonce the first
    consumed): one reads the configuration for every operation and the
    TTL keys one by one, the other once a close. Equal chain, results,
    transaction meta, nonce entries and accounts."""
    with per_transaction_reads():
        old = Net(cfg=CONFIGS[which]())
        try:
            seqs = _close_ledgers(old, 2)
            want = _state(old, seqs)
            loads = old.counter("soroban.config.load")
        finally:
            old.shutdown()
    new = Net(cfg=CONFIGS[which]())
    try:
        assert _close_ledgers(new, 2) == seqs
        got = _state(new, seqs)
        for seq in seqs:
            failed = sorted(kind for _, _, kind, res in new.results(seq)
                            if res == (FAILED, AUTH_FAILURE))
            assert failed == sorted(KINDS)
            assert sum(res == (SUCCESS, SOUND) for _, _, _, res
                       in new.results(seq)) == DEP["accounts"] - len(KINDS)
        # the deployment's close and the two transfer closes
        assert new.counter("soroban.config.load") == 3
    finally:
        new.shutdown()
    assert loads == 0          # the old node's loader is not the program's
    assert len(want["history"]) == 2 * DEP["accounts"]
    assert len(want["nonces"]) == 2 * (RELAYED - len(KINDS))
    for what in want:
        assert got[what] == want[what], what


# ------------------------------------------------------- (b) the counts --

def test_one_configuration_a_contract_close_none_a_payment_close():
    net = Net()
    try:
        shared = net.lm.root.soroban_stats
        built = net.counter("soroban.config.load")
        assert built == 1                      # set-up deployed a contract
        invoked = net.app.perf.report()["soroban.invoke"]["count"]
        net.close(net.next_ledger())
        assert net.counter("soroban.config.load") == built + 1
        assert net.app.perf.report()["soroban.invoke"]["count"] == \
            invoked + DEP["accounts"]
        assert shared.config is None
        # payments only: nothing is built, nothing is published
        submit(net.app, [f for f, *_ in net.traffic._pay.next_ledger()])
        net.app.manual_close()
        assert net.counter("soroban.config.load") == built + 1
        assert shared.config is None
    finally:
        net.shutdown()


def test_no_configuration_below_protocol_20():
    cfg = _config()
    cfg.LEDGER_PROTOCOL_VERSION = 19
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        pay = PaymentTraffic(43, app.config.network_id(), 6,
                             DEP["amounts"], DEP["starting_balance"])
        pay.fund(app, LedgerModel())
        submit(app, [f for f, *_ in pay.next_ledger()])
        app.manual_close()
        lm = app.ledger_manager
        assert lm.get_last_closed_ledger_header().ledgerVersion == 19
        assert "soroban.config.load" not in app.metrics.to_json()
        assert lm.root.soroban_stats.config is None
    finally:
        app.shutdown()


@pytest.mark.parametrize("which", ["sql", "full-cache"])
def test_apply_of_a_transfer_ledger_reads_no_key_by_itself(which):
    """Every key apply touches at the root was asked for in the close's
    one prefetch, a full cache or not: no point SELECT inside
    `applyTx`. With the TTL keys left out there is one for every
    consumed nonce's TTL entry: the mechanism, not the counter."""
    with per_transaction_reads():
        old = Net(cfg=CONFIGS[which]())
        try:
            with _reads_inside_apply(old) as reads:
                _close_ledgers(old, 2)
            # a nonce is consumed after the expiration, signer and
            # signature checks: the reused one gets that far too
            assert all(n >= RELAYED - len(KINDS) for n in reads), reads
        finally:
            old.shutdown()
    new = Net(cfg=CONFIGS[which]())
    try:
        published = new.counter("ledger.root.point.sql")
        with _reads_inside_apply(new) as reads:
            _close_ledgers(new, 2)
        assert reads == [0, 0]
        root = new.lm.root
        assert root.point_reads == 0           # published, and taken
        if which == "full-cache":
            assert len(root._cache) == root._cache.max_size == 8
        # what the counter has beyond apply is admission's: the herder
        # validates a submission against the root with no prefetch
        assert new.counter("ledger.root.point.sql") >= published
    finally:
        new.shutdown()


# ----------------------------------------------------- (c) the upgrades --

def _vote_config_upgrade(net, entry):
    """Publish `entry` as a config upgrade set (TEMPORARY contract data,
    as the settings-upgrade tool leaves it) and arm the vote: the next
    close carries the upgrade."""
    upgrade_set = ConfigUpgradeSet(updatedEntry=[entry])
    content_hash = sha256(upgrade_set.to_bytes())
    key = ConfigUpgradeSetKey(contractID=b"\x42" * 32,
                              contentHash=content_hash)
    lk = ConfigUpgradeSetFrame.ledger_key(key)
    with LedgerTxn(net.lm.root) as ltx:
        ltx.create(LedgerEntry(
            lastModifiedLedgerSeq=0,
            data=_LedgerEntryData(
                LedgerEntryType.CONTRACT_DATA,
                ContractDataEntry(
                    ext=ExtensionPoint(0),
                    contract=SCAddress(
                        SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                        b"\x42" * 32),
                    key=SCVal(SCValType.SCV_BYTES, bytes(content_hash)),
                    durability=ContractDataDurability.TEMPORARY,
                    val=SCVal(SCValType.SCV_BYTES,
                              upgrade_set.to_bytes()))),
            ext=_LedgerEntryExt(0)))
        ltx.create(LedgerEntry(
            lastModifiedLedgerSeq=0,
            data=_LedgerEntryData(
                LedgerEntryType.TTL,
                TTLEntry(keyHash=sha256(lk.to_bytes()),
                         liveUntilLedgerSeq=10_000)),
            ext=_LedgerEntryExt(0)))
        ltx.commit()
    r = net.app.command_handler.handle("upgrades", {
        "mode": "set", "upgradetime": "0",
        "configupgradesetkey": base64.b64encode(key.to_bytes()).decode()})
    assert r["status"] == "ok"


def _lowered_compute(net):
    with LedgerTxn(net.lm.root) as ltx:
        compute = ltx.load_without_record(LedgerKey.config_setting(
            ConfigSettingID.CONFIG_SETTING_CONTRACT_COMPUTE_V0)) \
            .data.value.value.clone()
    assert compute.txMaxInstructions > 4_000_000   # the transfers' declared
    compute.txMaxInstructions = 1_000
    return ConfigSettingEntry(
        ConfigSettingID.CONFIG_SETTING_CONTRACT_COMPUTE_V0, compute)


def _close_over_an_upgrade(net):
    _vote_config_upgrade(net, _lowered_compute(net))
    seqs = _close_ledgers(net, 2)
    with LedgerTxn(net.lm.root) as ltx:
        assert SorobanNetworkConfig(ltx).tx_max_instructions == 1_000
    return seqs, _state(net, seqs)


def test_a_config_upgrade_is_seen_by_the_next_close_and_not_by_its_own():
    """A close that carries a CONFIG upgrade lowering `txMaxInstructions`
    under what a transfer needs: its own transfers applied under the
    old value (upgrades run after the last transaction), the next
    close's under the new one and run out of budget. The close's one
    configuration is gone before the upgrade, so the result is the
    per-transaction loader's."""
    with per_transaction_reads():
        old = Net()
        try:
            seqs, want = _close_over_an_upgrade(old)
        finally:
            old.shutdown()
    new = Net()
    try:
        assert _close_over_an_upgrade(new) == (seqs, want)
        first, second = (new.results(seq) for seq in seqs)
        assert sum(res == (SUCCESS, SOUND) for *_, res in first) == \
            DEP["accounts"] - len(KINDS)
        exceeded = InvokeHostFunctionResultCode \
            .INVOKE_HOST_FUNCTION_RESOURCE_LIMIT_EXCEEDED.name
        assert {res for *_, res in second} == {(FAILED, exceeded)}
    finally:
        new.shutdown()


def test_the_protocol_20_upgrades_settings_reach_the_next_close():
    """A node below protocol 20 has no setting to read; the close that
    carries the upgrade creates them (`TESTING_SOROBAN_HIGH_LIMIT_
    OVERRIDE` scaled), after its transactions, and leaves no
    configuration of its own behind: the next close's first Soroban
    operation reads the new entries."""
    cfg = _config()
    cfg.LEDGER_PROTOCOL_VERSION = 19
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        lm = app.ledger_manager
        shared = lm.root.soroban_stats
        with LedgerTxn(lm.root) as ltx:
            assert SorobanNetworkConfig(ltx)._settings == {}
        app.herder.upgrades.set_parameters(UpgradeParameters(
            upgrade_time=0, protocol_version=20))
        app.manual_close()
        assert lm.get_last_closed_ledger_header().ledgerVersion == 20
        assert shared.config is None
        with LedgerTxn(lm.root) as ltx:
            with LedgerTxn(ltx) as op_ltx:
                shared.config = shared.UNREAD   # as a close's apply loop
                try:
                    seen = soroban_ops._load_config(op_ltx)
                    assert soroban_ops._load_config(op_ltx) is seen
                finally:
                    shared.config = None
        assert seen.tx_max_instructions == \
            100 * nc.INITIAL_TX_MAX_INSTRUCTIONS
        assert shared.config is None
    finally:
        app.shutdown()


# ------------------------------------------------ (d) a close that fails --

def test_a_close_that_raises_in_apply_leaves_no_configuration_behind():
    net = Net()
    try:
        lm, shared = net.lm, net.lm.root.soroban_stats
        apply_one, calls = lm._apply_one, []

        def third_raises(ltx, applicable, tx, verify):
            calls.append(tx)
            if len(calls) == 3:
                assert isinstance(shared.config, SorobanNetworkConfig)
                raise RuntimeError("apply broke")
            return apply_one(ltx, applicable, tx, verify)
        lm._apply_one = third_raises
        lcl = lm.get_last_closed_ledger_num()
        with pytest.raises(RuntimeError, match="apply broke"):
            net.close(net.next_ledger())
        assert lm.get_last_closed_ledger_num() == lcl
        assert shared.config is None
        # outside a close every reader builds its own, from its ledger
        with LedgerTxn(lm.root) as ltx:
            a, b = soroban_ops._load_config(ltx), \
                soroban_ops._load_config(ltx)
        assert a is not b and shared.config is None
    finally:
        net.lm._apply_one = apply_one
        net.shutdown()


# -------------------------------------- (e), (f) the footprint's TTL keys --

def _relayed(ledger):
    return next((f, tr) for f, tr, kind in ledger
                if tr.credential == "address" and kind is None)


def test_a_footprints_ttl_keys_are_its_contract_keys_hashes():
    net = Net()
    try:
        frame, tr = _relayed(net.next_ledger())
        instance = instance_key(net.traffic.contract)
        nonce = nonce_key(tr.frm, tr.nonce)
        accounts = {LedgerKey.account(PublicKey.ed25519(raw)).to_bytes()
                    for raw in (tr.source, tr.frm, tr.to)}
        fp = extract_footprint(frame)
        assert not fp.precise
        assert fp.keys == accounts | CONFIG_SETTING_KEYS | {
            instance.to_bytes(), nonce.to_bytes(),
            ttl_key_for(instance).to_bytes(), ttl_key_for(nonce).to_bytes()}
        # a payment's footprint has account keys and nothing else
        pay, *_ = net.traffic._pay.next_ledger()[0]
        assert all(LedgerKey.from_bytes(kb).disc == LedgerEntryType.ACCOUNT
                   for kb in extract_footprint(pay).keys)
    finally:
        net.shutdown()


@pytest.mark.parametrize("which", ["sql", "bucketlist"])
def test_prefetched_ttl_keys_answer_apply_and_a_lapsed_one_still_fails(
        which):
    """The close's prefetch holds the instance's TTL entry and every
    nonce key's absent one before the first transaction applies; an
    instance whose TTL has lapsed still fails every transfer with
    `entry archived`."""
    net = Net(cfg=CONFIGS[which]())
    try:
        lm, root = net.lm, net.lm.root
        instance_ttl = ttl_key_for(instance_key(net.traffic.contract))
        ledger = net.next_ledger()
        nonce_ttls = [ttl_key_for(nonce_key(tr.frm, tr.nonce)).to_bytes()
                      for _, tr, _ in ledger if tr.credential == "address"]
        assert len(nonce_ttls) == RELAYED
        held_at_apply = {}
        apply = lm._apply_transactions

        def looked(*a, **kw):
            held_at_apply.update(root._prefetched)
            return apply(*a, **kw)
        lm._apply_transactions = looked
        root._cache.clear()
        seq = net.close(ledger)
        assert held_at_apply[instance_ttl.to_bytes()] is not ledger_txn._ABSENT
        assert all(held_at_apply[kb] is ledger_txn._ABSENT
                   for kb in nonce_ttls)
        assert CONFIG_SETTING_KEYS <= set(held_at_apply)
        assert root._prefetched == {}          # gone with the commit
        assert net.run_model(seq) == 0
        # the instance's TTL lapses: prefetched all the same, and read
        with LedgerTxn(root) as ltx:
            ltx.load(instance_ttl).data.value.liveUntilLedgerSeq = seq
            ltx.commit()
        seq = net.close(net.next_ledger())
        archived = InvokeHostFunctionResultCode \
            .INVOKE_HOST_FUNCTION_ENTRY_ARCHIVED.name
        assert {res for *_, res in net.results(seq)} == {(FAILED, archived)}
    finally:
        net.shutdown()


# ------------------------------------------------- the root's prefetch --

def test_a_full_cache_does_not_turn_a_prefetch_away():
    """The cache holds 4 entries and is full; a prefetch of 25 keys (5
    of them absent) answers every lookup until the next commit, which
    drops what it held."""
    from test_ledger_txn import _acc_id, _account_entry
    db = Database(":memory:")
    db.initialize()
    with LedgerTxn(LedgerTxnRoot(db)) as ltx:
        for i in range(20):
            ltx.create(_account_entry(i, balance=1000 + i))
        ltx.commit()
    root = LedgerTxnRoot(db, cache_size=4)
    keys = [LedgerKey.account(_acc_id(i)) for i in range(25)]
    for key in keys[:4]:
        assert root._lookup(key.to_bytes()) is not None
    assert len(root._cache) == 4 and root.point_reads == 4
    assert root.prefetch(keys) == 25
    with LedgerTxn(root) as ltx:
        for _ in range(2):                     # evicted and asked again
            for i, key in enumerate(keys):
                le = ltx.load_without_record(key)
                assert (le.data.value.balance == 1000 + i) if i < 20 \
                    else le is None
        assert root.point_reads == 4 and len(root._cache) == 4
        ltx.load(keys[0]).data.value.balance = 7
        ltx.commit()
    assert root._prefetched == {}
    assert root._lookup(keys[0].to_bytes()).data.value.balance == 7
    assert root.point_reads == 4               # the commit cached it
    root._cache.clear()
    assert root._lookup(keys[19].to_bytes()).data.value.balance == 1019
    assert root.point_reads == 5               # nothing is held any more


def test_the_switch_to_the_bucket_list_drops_what_sql_prefetched():
    """An entry that lives only in buckets: SQL's prefetch held it as
    absent, and `serve_from_bucket_list` drops that with the rest."""
    from types import SimpleNamespace

    from stellar_core_tpu.xdr.ledger import BucketEntryType
    from test_ledger_txn import _acc_id, _account_entry
    db = Database(":memory:")
    db.initialize()
    root = LedgerTxnRoot(db)
    key = LedgerKey.account(_acc_id(1))
    root.prefetch([key])
    assert root._prefetched == {key.to_bytes(): ledger_txn._ABSENT}
    live = SimpleNamespace(disc=BucketEntryType.LIVEENTRY,
                           value=_account_entry(1, balance=5))
    root.serve_from_bucket_list(SimpleNamespace(
        get_entry=lambda k: live if k == key else None))
    assert root._prefetched == {}
    assert root._lookup(key.to_bytes()).data.value.balance == 5
    assert root.point_reads == 0               # the buckets', not SQL's
