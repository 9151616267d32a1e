"""Herder↔SCP integration: full Applications reaching consensus.

The pre-overlay analogue of the reference's Simulation tests: N real
Applications on one VirtualClock, SCP envelopes delivered herder-to-
herder, tx set fetches satisfied from peers' pending-envelope caches
(what ItemFetcher will do over the overlay).
"""

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.main import Application, Config, QuorumSetConfig
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment


PASSPHRASE = "herder-scp test network"


def make_network(n_nodes: int, threshold: int):
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    seeds = [SecretKey.from_seed(sha256(b"scpnet-%d" % i))
             for i in range(n_nodes)]
    node_ids = [s.public_key().raw for s in seeds]
    apps = []
    for i in range(n_nodes):
        cfg = Config()
        cfg.NETWORK_PASSPHRASE = PASSPHRASE
        cfg.NODE_SEED = seeds[i]
        cfg.NODE_IS_VALIDATOR = True
        cfg.RUN_STANDALONE = True
        cfg.FORCE_SCP = True
        cfg.MANUAL_CLOSE = False
        cfg.EXPECTED_LEDGER_CLOSE_TIME = 1.0
        cfg.MAX_TX_SET_SIZE = 100
        cfg.INVARIANT_CHECKS = [".*"]
        cfg.QUORUM_SET = QuorumSetConfig(threshold=threshold,
                                         validators=list(node_ids))
        apps.append(Application.create(clock, cfg))

    # message bus: emitted envelopes go straight to the other herders
    def wire(app):
        def broadcast(env):
            # deliver on next crank to avoid unbounded recursion
            def deliver():
                for other in apps:
                    if other is not app:
                        other.herder.recv_scp_envelope(env)
            clock.post(deliver)
        app.herder.broadcast_cb = broadcast

        def fetch_txset(h):
            def try_fetch():
                for other in apps:
                    ts = other.herder.pending_envelopes.get_tx_set(h)
                    if ts is not None:
                        app.herder.recv_tx_set(h, ts)
                        return
            clock.post(try_fetch)
        app.herder.pending_envelopes.request_txset = fetch_txset

        def fetch_qset(h):
            def try_fetch():
                for other in apps:
                    qs = other.herder.pending_envelopes.get_qset(h)
                    if qs is not None:
                        app.herder.recv_scp_quorum_set(h, qs)
                        return
            clock.post(try_fetch)
        app.herder.pending_envelopes.request_qset = fetch_qset

    for app in apps:
        wire(app)
    return clock, apps


def crank_until(clock, pred, max_virtual_seconds=60):
    deadline = clock.now() + max_virtual_seconds
    while not pred() and clock.now() < deadline:
        if clock.crank(False) == 0:
            clock.crank(True)  # advance virtual time to next timer
    return pred()


def all_at_ledger(apps, seq):
    return all(a.ledger_manager.get_last_closed_ledger_num() >= seq
               for a in apps)


@pytest.fixture
def net3():
    clock, apps = make_network(3, 2)
    for app in apps:
        app.start()
    yield clock, apps
    for app in apps:
        app.shutdown()


def test_three_validators_close_empty_ledgers(net3):
    clock, apps = net3
    assert crank_until(clock, lambda: all_at_ledger(apps, 3))
    hashes = {a.ledger_manager.get_last_closed_ledger_num():
              a.ledger_manager.get_last_closed_ledger_hash()
              for a in apps}
    # all nodes closed the same chain
    h2 = [a.ledger_manager.get_last_closed_ledger_hash() for a in apps
          if a.ledger_manager.get_last_closed_ledger_num() ==
          apps[0].ledger_manager.get_last_closed_ledger_num()]
    assert len(set(h2)) == 1


def test_payment_reaches_all_nodes(net3):
    clock, apps = net3
    assert crank_until(clock, lambda: all_at_ledger(apps, 2))
    master = m1.master_account(apps[0])
    dest = m1.AppAccount(apps[0], SecretKey.from_seed(b"\x21" * 32))
    frame = master.tx([op_create_account(dest.account_id, 10**11)])
    r = m1.submit(apps[0], frame)
    assert r["status"] == "PENDING"

    # no overlay in this harness, so the tx sits only in the submitting
    # node's queue and lands when THAT node wins a nomination round —
    # leader election is hash-driven, so crank until it does rather
    # than assuming a fixed slot
    def applied_everywhere():
        return all(m1.app_account_entry(a, dest.account_id) is not None
                   for a in apps)
    assert crank_until(clock, applied_everywhere,
                       max_virtual_seconds=120)
    # the new account exists on EVERY node with the same balance
    for app in apps:
        acc = m1.app_account_entry(app, dest.account_id)
        assert acc is not None and acc.balance == 10**11
    # ledger hashes agree
    seqs = {a.ledger_manager.get_last_closed_ledger_num() for a in apps}
    common = min(seqs)
    hs = set()
    for app in apps:
        row = app.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
            (common,))
        hs.add(bytes(row[0]))
    assert len(hs) == 1


def test_five_nodes_threshold_four():
    clock, apps = make_network(5, 4)
    for app in apps:
        app.start()
    try:
        assert crank_until(clock, lambda: all_at_ledger(apps, 2),
                           max_virtual_seconds=120)
    finally:
        for app in apps:
            app.shutdown()


# ---------------------------------------------------------------------------
# QuorumTracker (reference: herder/QuorumTracker.{h,cpp})
# ---------------------------------------------------------------------------

def _qt_node(i: int) -> bytes:
    return sha256(b"qt-node-%d" % i)


def _qt_qset(nodes, threshold, inner=()):
    from stellar_core_tpu.xdr.scp import SCPQuorumSet
    from stellar_core_tpu.xdr.types import PublicKey
    return SCPQuorumSet(threshold=threshold,
                        validators=[PublicKey.ed25519(n) for n in nodes],
                        innerSets=list(inner))


def test_quorum_tracker_bfs_and_distance():
    from stellar_core_tpu.herder.quorum_tracker import QuorumTracker
    me, a, b, c = (_qt_node(i) for i in range(4))
    # me -> {a, b}; a -> {c}; b's qset unknown
    qt = QuorumTracker(me, _qt_qset([a, b], 2))
    assert qt.is_node_definitely_in_quorum(a)
    assert qt.is_node_definitely_in_quorum(b)
    assert not qt.is_node_definitely_in_quorum(c)
    assert qt.expand(a, _qt_qset([c], 1))
    assert qt.is_node_definitely_in_quorum(c)
    assert qt.quorum_map[c].distance == 2
    assert qt.quorum_map[c].closest_validators == {a}
    # expanding an unknown node cannot be done incrementally
    d = _qt_node(9)
    assert not qt.expand(d, _qt_qset([me], 1))
    # conflicting re-expansion of a is rejected
    assert not qt.expand(a, _qt_qset([b], 1))


def test_quorum_tracker_rebuild_lookup():
    from stellar_core_tpu.herder.quorum_tracker import QuorumTracker
    me, a, b = (_qt_node(i) for i in (0, 1, 2))
    qsets = {a: _qt_qset([b], 1)}
    qt = QuorumTracker(me, _qt_qset([a], 1))
    qt.rebuild(lambda nid: qsets.get(nid))
    assert qt.is_node_definitely_in_quorum(b)
    assert qt.quorum_map[b].closest_validators == {a}
    j = qt.transitive_json()
    assert j["node_count"] == 3


def test_herder_quorum_json_has_transitive():
    clock, apps = make_network(3, 2)
    try:
        j = apps[0].herder.quorum_json()
        assert "transitive" in j
        # all three validators are in the local node's transitive quorum
        assert j["transitive"]["node_count"] == 3
    finally:
        for app in apps:
            app.shutdown()


def test_txset_validation_uses_batch_verifier():
    """With SIGNATURE_VERIFY_BACKEND=tpu the herder's txset validation
    routes every signature through one device batch (BASELINE.md config
    #2; collection point SURVEY.md §3.2)."""
    from stellar_core_tpu.main import Application, get_test_config
    from txtest_utils import op_create_account, op_payment

    cfg = get_test_config()
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    with Application.create(clock, cfg) as app:
        app.start()
        assert app.batch_verifier is not None
        assert app.herder.batch_verifier is app.batch_verifier
        master = m1.master_account(app)
        a = m1.AppAccount(app, SecretKey.from_seed(sha256(b"bv-a")))
        m1.submit(app, master.tx([
            op_create_account(a.account_id, 100_0000000)]))
        app.manual_close()

        m1.submit(app, master.tx([op_payment(a.muxed, 1234)]))
        lcl = app.ledger_manager.get_last_closed_ledger_header()
        from stellar_core_tpu.herder.tx_set import (
            SurgePricingLaneConfig, make_tx_set_from_transactions)
        txs = app.herder.tx_queue.get_transactions()
        frame, applicable, _ = make_tx_set_from_transactions(
            txs, lcl, app.config.network_id(),
            SurgePricingLaneConfig([lcl.maxTxSetSize]))
        # queue admission warmed the verify cache and the prevalidator
        # only dispatches cache MISSES; a remote validator receiving
        # this set has a cold cache, which is what dispatches the batch
        from stellar_core_tpu.crypto.keys import clear_verify_cache
        clear_verify_cache()
        assert app.herder.is_tx_set_valid(frame)
        counts = {k: app.metrics.new_counter(
            "herder.txset.prevalidate." + k).count
            for k in ("cached", "dispatched", "fallback")}
        assert counts == {"cached": 0, "dispatched": 1, "fallback": 0}
