"""A validator that is flooded before it is asked (ISSUE 45): one of
three validators is handed a slot's transactions in bursts through
`Herder.recv_transactions`, each burst one batch of the verify service
on the device path, then the slot's set and the other two's SCP
envelopes as in test_txset_follow.py; and every batch it sends runs on
a shape it loaded when it started (`Application._load_verify_shapes`).

The plain reference is the recording's publisher (two validators with
the native per-signature verifier), a second follower that admits the
same frames one at a time natively, `benchmark/reference/flood_model.py`
for the queue and the pure-Python oracle for verdicts. The device path
runs on the CPU at two small rungs: a verify-service flush of 16
(`VERIFY_MAX_BATCH`) and a largest bucket patched to 64 lanes. Sets of
48 payments arrive in bursts of 16."""

import copy
import hashlib
import json
import os

import pytest

from stellar_core_tpu.crypto.keys import SecretKey, clear_verify_cache
from stellar_core_tpu.herder.tx_queue import AddResult
from stellar_core_tpu.main import application
from stellar_core_tpu.ops import chunking

from benchmark.generators import txset_flood as fl
from benchmark.generators import txset_follow as tf
from benchmark.harness import node
from benchmark.reference import ed25519_oracle, flood_model
from benchmark.reference.flood_model import FloodModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ADDED = os.path.join(ROOT, "benchmark", "tests", "data", "added")
SEED = 4294967371
TXS = 48                       # one payment an account
BURST = 16
LARGEST = 64
PREFIX = "herder.txset.prevalidate."


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ADDED, "configs", "tiny-flood.json")) as f:
        doc = json.load(f)
    # one device of the suite's eight: the single-device verifier's
    # programs are the process's, traced once and not once a node
    doc["node"]["SIGNATURE_VERIFY_MESH"] = "single"
    assert doc["node"]["VERIFY_MAX_BATCH"] == BURST
    assert doc["deployment"]["flood"]["burst_txs"] == BURST
    return doc


@pytest.fixture(scope="module")
def rec(config, tmp_path_factory):
    """Ledgers 2..7 closed by validators 0 and 1: the upgrade, the
    account creation, four ledgers of 48 payments."""
    params = {"amounts": [10000, 20000], "recorded_ledgers": 4}
    return tf.record(config, params, SEED,
                     str(tmp_path_factory.mktemp("publisher")))


@pytest.fixture(autouse=True)
def small_rungs(monkeypatch):
    """The suite runs on the CPU backend, on which a node loads nothing
    unless asked: ask, at two small rungs."""
    monkeypatch.setattr(application, "SHAPE_LOADING_BACKENDS",
                        ("tpu", "cpu"))
    monkeypatch.setattr(chunking, "MAX_BUCKET", LARGEST)


def start(config, rec, where, **node_overrides):
    """Validator 2 at the ledger before the first payment ledger, with
    a verify cache that holds nothing of the publisher's."""
    doc = copy.deepcopy(config)
    doc["node"].update(node_overrides)
    app = tf.start_follower(doc, rec, str(where))
    app.said = []
    app.herder.broadcast_cb = app.said.append
    for slot in rec.slots:
        if slot.seq < rec.first_payment:
            tf.hand_over(app, slot)
    clear_verify_cache()
    app.base = node.counters(app)
    app.zones0 = node.zones(app)
    return app


@pytest.fixture
def follower(config, rec, tmp_path):
    app = start(config, rec, tmp_path / "node")
    try:
        yield app
    finally:
        app.shutdown()
        clear_verify_cache()


def payment_slots(rec):
    return [s for s in rec.slots if s.seq >= rec.first_payment]


def counters(app):
    out = {}
    node.add_into(out, node.counters(app), app.base)
    return out


def zones_of(app):
    out = {}
    node.add_into(out, node.zones(app), app.zones0)
    return out


jax_work = fl.jax_work


def flood(app, rec, bodies, bad_sig=None):
    """`bodies` through `recv_transactions` in bursts of 16; the
    outcomes in the flood model's words."""
    got = []
    for part in fl.bursts_of(bodies, BURST):
        bad = []
        res = app.herder.recv_transactions(
            [b.frame(rec.nid) for b in part], bad_sig=bad)
        got.extend(fl.outcome(r, b) for r, b in zip(res, bad))
        if bad_sig is not None:
            bad_sig.extend(bad)
    return got


def queued(app) -> set:
    return {tx.full_hash() for tx in app.herder.tx_queue.get_transactions()}


def new_model(rec) -> FloodModel:
    model = FloodModel()
    for a in rec.traffic.accounts:
        model.create(a.raw, rec.model.seq[a.raw])
    return model


def as_model_frames(bodies, sound=True):
    return [(b.key, b.account, b.seq, sound) for b in bodies]


# ------------------------------------------------- flooded and followed --

def test_flooded_node_and_native_node_hold_one_queue_and_close_one_chain(
        config, rec, follower, tmp_path):
    """The same frames through `recv_transactions` and the verify
    service, and one at a time through `recv_transaction` with the
    native verifier: the same queue, the publisher's headers, and the
    dictionary model's queue."""
    native = start(config, rec, tmp_path / "native",
                   SIGNATURE_VERIFY_BACKEND="native")
    try:
        assert native.verify_service is None
        model = new_model(rec)
        for slot in payment_slots(rec)[:3]:
            bodies = fl.slot_bodies(slot, rec.traffic.accounts)
            got = flood(follower, rec, bodies)
            want = model.burst(as_model_frames(bodies))
            assert got == want == [flood_model.PENDING] * TXS
            for b in bodies:
                assert native.herder.recv_transaction(b.frame(rec.nid)) \
                    == AddResult.ADD_STATUS_PENDING
            assert len(queued(follower)) == TXS == len(model.queue)
            assert queued(follower) == queued(native)
            for app in (follower, native):
                tf.hand_over(app, slot)
                assert app.ledger_manager.get_last_closed_ledger_hash() \
                    == slot.header_hash
            assert model.close([(b.key, b.account, b.seq)
                                for b in bodies]) == 0
            assert queued(follower) == queued(native) == set()
        c = counters(follower)
        # three bursts a ledger, each one flush and one device run
        assert c["crypto.verify.dispatch.batch"] == (9, 3.0 * TXS)
        assert c["crypto.verify.dispatch.padding"] == (9, 0.0)
        assert c["crypto.verify_service.flush.native"][0] \
            == c["crypto.verify_service.occupancy"][0] - 9
        assert c["crypto.verify_service.fallback"][0] == 0
    finally:
        native.shutdown()


def test_queue_holds_none_of_a_slots_frames_after_a_peers_set_closes(
        rec, follower):
    slot = payment_slots(rec)[0]
    bodies = fl.slot_bodies(slot, rec.traffic.accounts)
    flood(follower, rec, bodies)
    assert follower.herder.tx_queue.size_txs() == TXS
    tf.hand_over(follower, slot)
    assert follower.herder.tx_queue.size_txs() == 0
    assert not any(follower.herder.tx_queue.is_pending(
        b.frame(rec.nid).full_hash()) for b in bodies)


@pytest.mark.parametrize("flooded,cached,dispatched", [
    (True, TXS, 0), (False, 0, TXS)])
def test_set_validation_sends_the_device_what_the_flood_did_not_bring(
        rec, follower, flooded, cached, dispatched):
    slot = payment_slots(rec)[0]
    if flooded:
        flood(follower, rec, fl.slot_bodies(slot, rec.traffic.accounts))
    runs = counters(follower).get("crypto.verify.dispatch.batch",
                                  (0, 0.0))[0]
    tf.hand_over(follower, slot)
    c = counters(follower)
    assert c[PREFIX + "cached"][0] == cached
    assert c[PREFIX + "dispatched"][0] == dispatched
    assert c[PREFIX + "fallback"][0] == 0
    # a whole set is one run of the largest bucket, a warm one none
    assert c["crypto.verify.dispatch.batch"][0] - runs == (dispatched > 0)
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == slot.header_hash


def test_withheld_transactions_are_the_sets_only_misses(rec, follower):
    slot = payment_slots(rec)[0]
    bodies = fl.slot_bodies(slot, rec.traffic.accounts)
    flood(follower, rec, bodies[:32])
    tf.hand_over(follower, slot)
    c = counters(follower)
    assert (c[PREFIX + "cached"][0], c[PREFIX + "dispatched"][0]) \
        == (32, 16)
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == slot.header_hash
    assert follower.herder.tx_queue.size_txs() == 0


def test_adversarial_burst_bad_sig_is_the_oracles_and_a_duplicate_is_not_verified_twice(
        rec, follower):
    slot = payment_slots(rec)[0]
    bodies = fl.slot_bodies(slot, rec.traffic.accounts)
    model = new_model(rec)
    first = bodies[:BURST]
    assert flood(follower, rec, first) \
        == model.burst(as_model_frames(first))
    before = counters(follower)["crypto.verify.dispatch.batch"]
    # 3 flipped, 2 delivered again, 11 new
    adv = [(b, True) for b in bodies[16:27]] \
        + [(b.flipped(), False) for b in bodies[40:43]] \
        + [(b, True) for b in (first[5], first[11])]
    adv = adv[::2] + adv[1::2]
    bad = []
    got = flood(follower, rec, [b for b, _ in adv], bad_sig=bad)
    sound = [ed25519_oracle.verify(*fl._tuple_of(b.raw, rec.nid))
             for b, _ in adv]
    assert sound == [meant for _, meant in adv]
    assert bad == [not ok for ok in sound]
    assert got == model.burst([(b.key, b.account, b.seq, ok)
                               for (b, _), ok in zip(adv, sound)])
    assert (got.count(flood_model.BAD_SIG),
            got.count(flood_model.DUPLICATE),
            got.count(flood_model.PENDING)) == (3, 2, 11)
    # none of the flipped frames is queued
    assert len(queued(follower)) == 16 + 11 == len(model.queue)
    after = counters(follower)["crypto.verify.dispatch.batch"]
    assert (after[0] - before[0], after[1] - before[1]) == (1, 14.0)
    # the rest of the slot floods in, the set closes, the counters say
    # what happened to every frame
    flood(follower, rec, bodies[27:])
    tf.hand_over(follower, slot)
    assert follower.ledger_manager.get_last_closed_ledger_hash() \
        == slot.header_hash
    c = counters(follower)
    assert [c["herder.flood." + k][0]
            for k in ("received", "admitted", "duplicate", "badSig")] \
        == [TXS + 5, TXS, 2, 3]


def test_burst_zones_are_one_a_burst_with_the_wait_inside(rec, follower):
    slot = payment_slots(rec)[0]
    flood(follower, rec, fl.slot_bodies(slot, rec.traffic.accounts))
    z = zones_of(follower)
    assert z["herder.recvTransactions"][0] == 3 \
        == z["herder.recvTransactions.verify"][0]
    assert 0.0 < z["herder.recvTransactions.verify"][1] \
        <= z["herder.recvTransactions"][1]
    # no zone round a frame: the single call's is published at the close
    assert z.get("herder.recvTransaction", (0, 0.0))[0] == 0
    tf.hand_over(follower, slot)
    assert zones_of(follower)["herder.recvTransaction"][0] == TXS
    assert follower.herder.recv_transactions([]) == []


# ------------------------------------------------------ loaded shapes --

def signed_tuples(n: int) -> list:
    out = []
    for i in range(n):
        key = SecretKey.from_seed(hashlib.sha256(b"flood-%d" % i).digest())
        msg = hashlib.sha256(b"message-%d" % i).digest()
        out.append((key.public_key().raw, key.sign(msg), msg))
    return out


@pytest.mark.parametrize("how,n", [
    ("burst", 1), ("burst", 15), ("burst", 16), ("burst", 200),
    ("burst", 257), ("set", 600), ("set", 20), ("set", 64), ("set", 65)])
def test_live_batches_compile_nothing_after_start_up(follower, how, n):
    """Whatever the size of a burst through the verify service or of a
    received set's cache misses, the batch runs on a shape the node
    loaded when it started: 16 lanes (a full flush) or 64 (the largest
    bucket; larger batches are chunks of it)."""
    assert follower.batch_verifier.loaded_shapes == [BURST, LARGEST]
    work = jax_work()
    tuples = signed_tuples(n)
    if how == "burst":
        verdicts = [f.result()
                    for f in follower.verify_service.submit_many(tuples)]
    else:
        verdicts = follower.batch_verifier.verify_tuples(tuples)
    assert list(verdicts) == [True] * n
    assert jax_work() == work
    c = counters(follower)
    assert node.counters(follower)["crypto.verify.shape.missed"][0] == 0
    runs, lanes = c.get("crypto.verify.dispatch.batch", (0, 0.0))
    pad = c.get("crypto.verify.dispatch.padding", (0, 0.0))[1]
    if how == "burst":
        # flushes of 16 and a remainder, which runs on the host under
        # VERIFY_DEVICE_MIN_BATCH (8) and in the 16-lane shape above it
        rest = n % BURST
        assert runs == n // BURST + (rest >= 8)
        assert lanes + pad == runs * BURST
        assert c["crypto.verify_service.flush.native"][0] == (0 < rest < 8)
    else:
        assert runs == -(-n // LARGEST) and lanes == n
        assert lanes + pad == runs * LARGEST


def test_second_node_of_a_process_pays_no_second_trace(
        config, rec, follower, tmp_path):
    work = jax_work()
    second = start(config, rec, tmp_path / "second")
    try:
        assert jax_work() == work
        c = node.counters(second)
        assert c["crypto.verify.shape.loaded"][0] == 2
        assert c["crypto.verify.shape.missed"][0] == 0
        assert node.zones(second)["app.start.loadShapes"][0] == 1
    finally:
        second.shutdown()


def test_manual_close_node_loads_the_largest_bucket_alone(
        config, rec, tmp_path):
    """It tracks no network and nobody floods it: its own 20 tuples run
    on the one shape it has."""
    doc = copy.deepcopy(config)
    doc["node"].update(MANUAL_CLOSE=True, NODE_IS_VALIDATOR=False)
    doc["node"].pop("QUORUM_SET")
    app = node.start_node(node.make_config(doc["node"],
                                           str(tmp_path / "manual")))
    try:
        assert app.batch_verifier.loaded_shapes == [LARGEST]
        assert node.counters(app)["crypto.verify.shape.loaded"][0] == 1
        work = jax_work()
        assert app.batch_verifier.verify_tuples(signed_tuples(20)) \
            == [True] * 20
        assert jax_work() == work
        c = node.counters(app)
        assert c["crypto.verify.dispatch.padding"] == (1, LARGEST - 20.0)
        assert c["crypto.verify.shape.missed"][0] == 0
    finally:
        app.shutdown()


def test_node_that_loads_nothing_pads_a_batch_to_its_own_bucket(
        config, rec, tmp_path, monkeypatch):
    """The control of the benchmark's cell: with the start-up load
    switched off a batch pads to its own power of two (and the first of
    a shape the process has never run is counted, which the cell's
    rehearsal shows: benchmark/tests/test_flood_cell.py)."""
    monkeypatch.setattr(application, "SHAPE_LOADING_BACKENDS", ("tpu",))
    app = start(config, rec, tmp_path / "unloaded")
    try:
        c = node.counters(app)
        assert c["crypto.verify.shape.loaded"][0] == 0
        # 9 tuples: a 16-lane batch; counted as missed only where no
        # test before this one has run that shape in this process
        from stellar_core_tpu.ops import verifier
        known = any(lanes == 16 for _, lanes in verifier._SHAPES_RUN)
        work = jax_work()
        assert app.batch_verifier.verify_tuples(signed_tuples(9)) \
            == [True] * 9
        c = node.counters(app)
        assert c["crypto.verify.dispatch.padding"][1] == 7.0
        assert c["crypto.verify.shape.missed"][0] == (0 if known else 1)
        assert (jax_work() == work) == known
    finally:
        app.shutdown()
