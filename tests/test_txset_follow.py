"""A validator that follows (ISSUE 39): one of three validators is
handed each slot's tx set and the other two's recorded SCP envelopes
through `Herder.recv_tx_set` / `recv_scp_envelope`, validates the set
as one device batch, votes, externalizes and closes.

The plain reference is the recording's publisher (two validators with
the native per-signature verifier, `benchmark/generators/txset_follow.py`),
`benchmark/reference/ledger_model.py`'s dictionary for the accounts and
the pure-Python oracle for verdicts. The device path runs on the CPU
with the largest bucket patched to 16 lanes: a set of 52 signatures is
three chunks and a remainder of 4."""

import json
import os

import pytest

from stellar_core_tpu.crypto import keys as crypto_keys
from stellar_core_tpu.crypto.keys import clear_verify_cache
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.scp import ValidationLevel
from stellar_core_tpu.util.perf import ON_CPU

from benchmark.generators import txset_follow as tf
from benchmark.harness import node
from benchmark.reference import ed25519_oracle
from benchmark.reference.ledger_model import LedgerModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ADDED = os.path.join(ROOT, "benchmark", "tests", "data", "added")
SEED = 4294967357
SIGS = 52                      # one payment an account
PREFIX = "herder.txset.prevalidate."


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ADDED, "configs", "tiny-txset.json")) as f:
        doc = json.load(f)
    # one device of the suite's eight: the single-device verifier's
    # programs are the process's, traced once and not once a node
    doc["node"]["SIGNATURE_VERIFY_MESH"] = "single"
    return doc


@pytest.fixture(scope="module")
def rec(config, tmp_path_factory):
    """Ledgers 2..9 closed by validators 0 and 1: the upgrade, the
    account creation, six ledgers of 52 payments."""
    params = {"amounts": [10000, 20000], "recorded_ledgers": 6}
    return tf.record(config, params, SEED,
                     str(tmp_path_factory.mktemp("publisher")))


@pytest.fixture(autouse=True)
def tiny_chunks(monkeypatch):
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)


@pytest.fixture
def follower(config, rec, tmp_path):
    """Validator 2, at the ledger before the first payment ledger, with
    a verify cache that holds nothing of the publisher's."""
    app = tf.start_follower(config, rec, str(tmp_path / "node"))
    app.said = []
    app.herder.broadcast_cb = app.said.append
    for slot in rec.slots:
        if slot.seq < rec.first_payment:
            tf.hand_over(app, slot)
    clear_verify_cache()
    app.base = node.counters(app)
    app.zones0 = node.zones(app)
    try:
        yield app
    finally:
        app.shutdown()
        clear_verify_cache()


def payment_slots(rec):
    return [s for s in rec.slots if s.seq >= rec.first_payment]


def counters(app):
    """Since the fixture handed the node over."""
    out = {}
    node.add_into(out, node.counters(app), getattr(app, "base", None))
    return out


def zones_of(app):
    out = {}
    node.add_into(out, node.zones(app), getattr(app, "zones0", None))
    return out


def count(app, name):
    return counters(app).get(name, (0, 0.0))[0]


def spy_on_batches(app):
    """Every synchronous batch of the node's device verifier:
    [(tuples, verdicts)], the call passed through unchanged."""
    seen = []
    real = app.batch_verifier.verify_tuples

    def verify_tuples(items):
        out = real(items)
        seen.append((list(items), [bool(v) for v in out]))
        return out
    app.batch_verifier.verify_tuples = verify_tuples
    return seen


def test_header_chain_equals_the_publishers_at_every_ledger(rec, follower):
    lm = follower.ledger_manager
    model = LedgerModel()
    model.balance, model.seq = dict(rec.model.balance), dict(rec.model.seq)
    followed = payment_slots(rec)[:4]
    for slot in followed:
        took, late = tf.hand_over(follower, slot)
        assert took > 0.0 and 0 <= late < len(slot.envelopes)
        assert lm.get_last_closed_ledger_num() == slot.seq
        assert lm.get_last_closed_ledger_hash() == slot.header_hash
        for s, d, amount in slot.payments:
            model.pay(rec.traffic.accounts[s].raw,
                      rec.traffic.accounts[d].raw, amount)
    lm.join_completion()
    on_disk = {int(seq): bytes(h) for seq, h in follower.database.query_all(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
    last = followed[-1].seq
    assert last >= 7
    assert {seq: h for seq, h in on_disk.items()} == \
        {seq: h for seq, h in rec.hash_at.items() if seq <= last}
    observed = node.account_states(
        follower, [a.raw for a in rec.traffic.accounts])
    assert model.differences(observed) == 0
    # it confirmed what the other two did, as one of the quorum: an
    # EXTERNALIZE of its own a slot, and it proposed nothing
    kinds = [(e.statement.slotIndex, e.statement.pledges.disc.name)
             for e in follower.said]
    for slot in followed:
        assert (slot.seq, "SCP_ST_EXTERNALIZE") in kinds
    assert not any(k == "SCP_ST_NOMINATE" for _, k in kinds)
    # the slot's SCP messages are on disk with the ledger they closed
    rows = follower.database.query_all(
        "SELECT ledgerseq, count(*) FROM scphistory GROUP BY ledgerseq")
    assert {int(s) for s, _ in rows} == set(range(2, last + 1))
    assert count(follower, "database.tail.busy") == 0


def test_set_spans_three_chunks_and_a_remainder_verdicts_in_order(
        rec, follower):
    seen = spy_on_batches(follower)
    good, nxt = payment_slots(rec)[:2]
    tf.hand_over(follower, good)
    assert len(seen) == 1
    tuples, verdicts = seen[0]
    assert len(tuples) == SIGS == len(chunking.chunk_bounds(SIGS, 16)) \
        * 16 - 12 and verdicts == [True] * SIGS
    c = counters(follower)
    assert c["crypto.verify.dispatch.chunks"][0] == 4
    assert c["crypto.verify.dispatch.batch"] == (4, float(SIGS))
    assert c["crypto.verify.dispatch.padding"] == (4, 12.0)
    # flips on both sides of every chunk boundary and in the remainder
    flipped = [0, 15, 16, 31, 32, 47, 48, 51]
    bad = tf.flip_signatures(nxt.frame, flipped, rec.nid)
    assert bad.get_contents_hash() != nxt.set_hash
    follower.herder.recv_tx_set(bad.get_contents_hash(), bad)
    assert follower.herder.is_tx_set_valid(bad) is False
    tuples, verdicts = seen[1]
    assert tuples == tf.set_tuples(bad, rec.nid)
    want = [ed25519_oracle.verify(*t) for t in tuples]
    assert verdicts == want
    assert [i for i, ok in enumerate(want) if not ok] == flipped


def test_bit_flipped_set_is_invalid_and_gets_no_vote(rec, follower):
    first, nxt = payment_slots(rec)[:2]
    tf.hand_over(follower, first)
    said = len(follower.said)
    bad = tf.flip_signatures(nxt.frame, [29], rec.nid)
    value, env = tf.prepare_naming(rec, nxt, bad.get_contents_hash())
    follower.herder.recv_tx_set(bad.get_contents_hash(), bad)
    follower.herder.recv_scp_envelope(env)
    assert follower.herder.scp_driver.validate_value(
        nxt.seq, value, False) == ValidationLevel.kInvalidValue
    assert len(follower.said) == said
    assert follower.ledger_manager.get_last_closed_ledger_num() == first.seq
    assert count(follower, PREFIX + "dispatched") == 2 * SIGS
    # the set as it was recorded, from the same two validators, is
    # voted for and closed: the refusal was the signature's
    tf.hand_over(follower, nxt)
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == nxt.header_hash
    assert {e.statement.slotIndex for e in follower.said[said:]} \
        == {nxt.seq}


def test_structurally_invalid_set_dispatches_nothing(rec, follower):
    """A set built on another ledger than the node's last closed one
    fails before any signature is reached: no batch, no count."""
    first, second = payment_slots(rec)[:2]
    seen = spy_on_batches(follower)
    follower.herder.recv_tx_set(second.set_hash, second.frame)
    assert follower.herder.is_tx_set_valid(second.frame) is False
    assert seen == []
    for k in ("cached", "dispatched", "fallback"):
        assert count(follower, PREFIX + k) == 0
    zones = zones_of(follower)
    assert zones["herder.txset.validate"][0] == 1
    assert count(follower, "herder.txset.receivedToValidated") == 1
    # on its own ledger the same set is valid
    tf.hand_over(follower, first)
    tf.hand_over(follower, second)
    assert count(follower, PREFIX + "dispatched") == 2 * SIGS


def test_half_cached_set_dispatches_exactly_its_misses(rec, follower):
    slot = payment_slots(rec)[0]
    tuples = tf.set_tuples(slot.frame, rec.nid)
    for t in tuples[::2]:
        crypto_keys.seed_verify_cache(*t, True)
    seen = spy_on_batches(follower)
    tf.hand_over(follower, slot)
    assert [t for t, _ in seen] == [tuples[1::2]]
    assert count(follower, PREFIX + "cached") == SIGS // 2
    assert count(follower, PREFIX + "dispatched") == SIGS // 2
    assert count(follower, PREFIX + "fallback") == 0
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == slot.header_hash


def test_failing_batch_gives_the_same_verdicts_natively_and_is_counted(
        rec, follower):
    first, nxt = payment_slots(rec)[:2]

    def broken(items):
        raise RuntimeError("device verifier down")
    follower.batch_verifier.verify_tuples = broken
    tf.hand_over(follower, first)
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == first.header_hash
    assert count(follower, PREFIX + "fallback") == SIGS
    assert count(follower, PREFIX + "dispatched") == 0
    bad = tf.flip_signatures(nxt.frame, [3], rec.nid)
    follower.herder.recv_tx_set(bad.get_contents_hash(), bad)
    assert follower.herder.is_tx_set_valid(bad) is False
    assert count(follower, PREFIX + "fallback") == 2 * SIGS
    # the set as recorded: what the native verifier answered for the
    # corrupted copy before it met the flipped signature is in the
    # verify cache by now, the rest falls back again
    follower.herder.recv_tx_set(nxt.set_hash, nxt.frame)
    assert follower.herder.is_tx_set_valid(nxt.frame) is True
    cached = count(follower, PREFIX + "cached")
    assert 0 <= cached < SIGS
    assert count(follower, PREFIX + "fallback") == 3 * SIGS - cached
    assert count(follower, PREFIX + "dispatched") == 0


def test_zone_counters_and_timer_count_once_a_validated_set(rec, follower):
    followed = payment_slots(rec)[:3]
    follower.flight_recorder.start(capacity=1 << 16)
    for slot in followed:
        tf.hand_over(follower, slot)
    follower.flight_recorder.stop()
    zones = zones_of(follower)
    # a quorum's worth of envelopes named each set: one validation
    assert sum(len(s.envelopes) for s in followed) >= 3 * 6
    assert zones["herder.txset.validate"][0] == 3
    measured, on_cpu = zones["herder.txset.validate" + ON_CPU]
    assert measured == 3 and 0.0 <= on_cpu \
        <= 1.02 * zones["herder.txset.validate"][1] + 1e-3
    c = counters(follower)
    assert c[PREFIX + "dispatched"][0] == 3 * SIGS
    assert c[PREFIX + "cached"][0] == c[PREFIX + "fallback"][0] == 0
    n, seconds = c["herder.txset.receivedToValidated"]
    assert n == 3 and 0.0 < seconds
    # the zone lies inside `recv_tx_set` to the verdict
    assert zones["herder.txset.validate"][1] <= seconds + 1e-3
    spans = [ev for ev in
             follower.flight_recorder.to_chrome_trace()["traceEvents"]
             if ev.get("name") == "herder.txset.validate"
             and ev["ph"] == "B"]
    assert [ev["args"]["slot"] for ev in spans] == \
        [s.seq for s in followed]
    assert all(ev["args"]["txs"] == SIGS for ev in spans)


@pytest.mark.parametrize("cleared", [True, False])
def test_publishers_cache_in_the_same_process(config, rec, tmp_path,
                                              cleared):
    """The verify cache is process-wide: a publisher that has verified
    the same signatures natively in this process empties the follower's
    batch (PR 27's reading), unless the cache is cleared after set-up."""
    app = tf.start_follower(config, rec, str(tmp_path / "node"))
    try:
        slots = payment_slots(rec)
        for slot in rec.slots:
            if slot.seq < rec.first_payment:
                tf.hand_over(app, slot)
        clear_verify_cache()
        app.base = node.counters(app)
        # what the publisher's admission left behind
        for slot in slots[:2]:
            for t in tf.set_tuples(slot.frame, rec.nid):
                crypto_keys.seed_verify_cache(*t, True)
        if cleared:
            clear_verify_cache()
        for slot in slots[:2]:
            tf.hand_over(app, slot)
        dispatched = count(app, PREFIX + "dispatched")
        cached = count(app, PREFIX + "cached")
        assert (dispatched, cached) == \
            ((2 * SIGS, 0) if cleared else (0, 2 * SIGS))
    finally:
        app.shutdown()
        clear_verify_cache()


def test_apply_reads_the_validated_sets_verdicts_not_the_cache(
        rec, follower, monkeypatch):
    """The verify cache evicts at random once it is full (65,535
    entries: thirteen ledgers of 5,000): a verdict the validation seeded
    may be gone when apply asks. Apply is handed the validated set's own
    table, so no transaction signature verifies natively."""
    from stellar_core_tpu.util.cache import RandomEvictionCache
    monkeypatch.setattr(crypto_keys, "_verify_cache",
                        RandomEvictionCache(8))
    for slot in payment_slots(rec)[:3]:
        tf.hand_over(follower, slot)
    natives, _ = zones_of(follower).get("crypto.verify.native", (0, 0.0))
    # an envelope's own signature is verified natively, and a count
    # reaches the node's zone at its next close: those of the ledger
    # before the first may fall in
    envelopes = sum(len(s.envelopes) for s in rec.slots
                    if rec.first_payment - 1 <= s.seq
                    < rec.first_payment + 3)
    assert natives <= envelopes < SIGS


def test_received_sets_are_forgotten_with_their_slots(rec, follower):
    for slot in payment_slots(rec)[:4]:
        tf.hand_over(follower, slot)
    pe = follower.herder.pending_envelopes
    remember = follower.config.MAX_SLOTS_TO_REMEMBER
    assert len(pe._txsets) <= remember + 1
    for seq in range(10, 10 + 3 * remember):
        pe.add_tx_set(b"%032d" % seq, object())
        pe.slot_closed(seq, remember)
    assert len(pe._txsets) <= remember + 1
    assert pe.get_tx_set(b"%032d" % (10 + 3 * remember - 1)) is not None


def envelope_naming(rec, seq, set_hash):
    """Validator 0's PREPARE of a recorded slot, moved to slot `seq` and
    naming `set_hash` (the fetch tracker reads it, and verifies no
    signature)."""
    _, env = tf.prepare_naming(rec, payment_slots(rec)[0], set_hash)
    env.statement.slotIndex = seq
    return env


def test_node_that_lags_keeps_the_sets_of_slots_still_to_come(rec, follower):
    """A node stuck at its last closed ledger is sent the envelopes and
    sets of slots far ahead (`LEDGER_VALIDITY_BRACKET` lets it). A set
    lives as long as the highest slot that named it, not as long as a
    count of closes since it arrived: when the node closes the slots one
    by one, each still finds its set."""
    pe = follower.herder.pending_envelopes
    remember = follower.config.MAX_SLOTS_TO_REMEMBER
    asked = []
    pe.request_txset = asked.append
    lcl = follower.ledger_manager.get_last_closed_ledger_num()
    ahead = {lcl + k: b"%032d" % k for k in range(2, 21)}
    assert len(ahead) > remember + 1
    for seq, h in ahead.items():
        pe.recv_scp_envelope(envelope_naming(rec, seq, h))
        assert asked[-1] == h
        pe.add_tx_set(h, object())
    assert pe.ready_slots() == sorted(ahead)
    for closed in range(lcl + 1, lcl + 21):
        pe.slot_closed(closed, remember)
        for seq, h in ahead.items():
            assert (pe.get_tx_set(h) is not None) \
                == (seq > closed - remember), (closed, seq)
    # and one named again by a later slot stays for that slot
    pe.recv_scp_envelope(envelope_naming(rec, lcl + 30, ahead[lcl + 20]))
    pe.slot_closed(lcl + 20 + remember, remember)
    assert pe.get_tx_set(ahead[lcl + 20]) is not None
    assert len(asked) == len(ahead)


def test_follower_that_lags_externalizes_every_slot_and_fetches_once(
        config, rec, tmp_path):
    """The same through the node, with a memory of three slots: it knows
    a first envelope and the set of five slots ahead while it is stuck;
    then the slots are decided one by one. Every ledger closes on the
    publisher's header and no set is asked for a second time."""
    doc = dict(config, node=dict(config["node"], MAX_SLOTS_TO_REMEMBER=3))
    app = tf.start_follower(doc, rec, str(tmp_path / "node"))
    try:
        for slot in rec.slots:
            if slot.seq < rec.first_payment:
                tf.hand_over(app, slot)
        clear_verify_cache()
        herder, lm = app.herder, app.ledger_manager
        slots = payment_slots(rec)
        frames = {s.set_hash: s.frame for s in slots}
        asked, answered = [], 0

        def hand(env):
            nonlocal answered
            herder.recv_scp_envelope(env)
            # the overlay's answer to GET_TX_SET, after the call
            for h in asked[answered:]:
                herder.recv_tx_set(h, frames[h])
            answered = len(asked)
        herder.pending_envelopes.request_txset = asked.append
        for slot in slots[1:]:
            hand(slot.envelopes[0])
        assert asked == [s.set_hash for s in slots[1:]]
        assert lm.get_last_closed_ledger_num() == rec.first_payment - 1
        for slot in slots:
            for env in slot.envelopes:
                hand(env)
            assert lm.get_last_closed_ledger_num() == slot.seq
            assert lm.get_last_closed_ledger_hash() == slot.header_hash
        assert sorted(asked) == sorted(frames)
    finally:
        app.shutdown()
        clear_verify_cache()


def test_generators_tuples_are_the_programs_in_the_sets_order(rec):
    """`set_tuples` reads key, signature and signed hash off the XDR
    itself; the program's collector, which the node's batch is made by,
    gives the same tuples in the same order."""
    from stellar_core_tpu.tx.signature_checker import \
        collect_signature_tuples
    for slot in payment_slots(rec)[:2]:
        mine = tf.set_tuples(slot.frame, rec.nid)
        assert len(mine) == SIGS
        assert mine == collect_signature_tuples(
            [f for f, _ in slot.frame._frames_with_base_fee()], rec.nid)
        assert all(ed25519_oracle.verify(*t) for t in mine[:3])


def test_the_cells_generator_is_not_named_in_the_program():
    import subprocess
    out = subprocess.run(
        ["grep", "-rn", "-e", "txset-5000", "-e", "txset_follow",
         os.path.join(ROOT, "stellar_core_tpu"), "--include=*.py"],
        capture_output=True, text=True)
    assert out.stdout == ""
