"""Snapshot-consistent read-serving tier (query/, ISSUE 17).

Covers the tentpole contracts: a reader holding the snapshot of
ledger N sees byte-identical results no matter how many ledgers close
after it while a late reader sees the newest seq; bucket GC honors
live read-snapshot pins across churn and collects once the last
reader drops; the tx-status store is fed from the deferred-completion
stream and stays bounded by capacity and TTL; the QueryService sheds
at the admission door (queue-full and controller), times out past the
deadline, and hedges slow lookups; the read shed ladder ramps on a
read_p99 breach while the write ladder stays untouched; bulk seeding
installs synthetic accounts the read path can serve while ledgers
keep closing; and the bucket-index meters drain into the registry.
"""

import threading
import time

from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.crypto.strkey import StrKey
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.query.tx_status import TxStatusStore
from stellar_core_tpu.simulation.load_generator import (
    LoadGenerator, bulk_account_id, seed_accounts_bulk)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock


def _app(cfg=None):
    cfg = cfg or get_test_config()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _pay_app():
    """App with a few loadgen accounts whose balances move per close."""
    app = _app()
    gen = LoadGenerator(app)
    gen.generate_accounts(4)
    app.manual_close()
    gen.sync_account_seqs()
    return app, gen


# ------------------------------------------------------- snapshot reads --

def test_reader_holding_snapshot_sees_frozen_bytes():
    app, gen = _pay_app()
    try:
        svc = app.query_service
        target = gen.accounts[0].key.public_key().raw
        snap_n = app.snapshots.acquire()
        seq_n = snap_n.ledger_seq
        before = svc.query_account(target, snapshot=snap_n)
        assert before["found"] and before["ledger_seq"] == seq_n
        # three more ledgers rewrite the account's balance
        for _ in range(3):
            gen.generate_payments(4)
            app.manual_close()
            gen.sync_account_seqs()
        # the held snapshot answers byte-identically at seq N
        for _ in range(2):
            again = svc.query_account(target, snapshot=snap_n)
            assert again["ledger_seq"] == seq_n
            assert again["entry_xdr"] == before["entry_xdr"]
        # a late reader (no pinned snapshot) sees N+3 and new bytes
        late = svc.query_account(target)
        assert late["found"] and late["ledger_seq"] == seq_n + 3
        assert late["entry_xdr"] != before["entry_xdr"]
        app.snapshots.release(snap_n)
    finally:
        app.shutdown()


def test_every_response_seq_names_a_closed_ledger():
    app, gen = _pay_app()
    try:
        closed = {app.ledger_manager.get_last_closed_ledger_num()}
        app.ledger_manager.closed_hooks.insert(
            0, lambda h, _: closed.add(h.ledgerSeq))
        target = gen.accounts[1].key.public_key().raw
        for _ in range(3):
            gen.generate_payments(4)
            app.manual_close()
            gen.sync_account_seqs()
            res = app.query_service.query_account(target)
            assert res["ledger_seq"] in closed
    finally:
        app.shutdown()


def test_missing_account_not_found_with_seq():
    app = _app()
    try:
        res = app.query_service.query_account(sha256(b"nobody-home"))
        assert res["found"] is False
        assert res["ledger_seq"] == \
            app.ledger_manager.get_last_closed_ledger_num()
        assert res["entry_xdr"] is None
    finally:
        app.shutdown()


# ------------------------------------------------------------ GC pinning --

def test_bucket_gc_honors_snapshot_pins_across_churn():
    app, gen = _pay_app()
    try:
        snap_n = app.snapshots.acquire()
        # churn: enough closes that level-0/1 spills replace the
        # buckets snap_n captured in the live list
        for _ in range(6):
            gen.generate_payments(4)
            app.manual_close()
            gen.sync_account_seqs()
        bm = app.bucket_manager
        orphaned = snap_n.bucket_hashes() - bm.referenced_hashes()
        assert orphaned, "churn never orphaned a snapshot bucket"
        bm.forget_unreferenced_buckets()
        for h in orphaned:
            assert h in bm._buckets, \
                "GC dropped a bucket a live snapshot still reads"
        # consistency survives the GC pass: the pinned snapshot still
        # answers at its own seq
        target = gen.accounts[0].key.public_key().raw
        res = app.query_service.query_account(target, snapshot=snap_n)
        assert res["found"] and res["ledger_seq"] == snap_n.ledger_seq
        app.snapshots.release(snap_n)
        bm.forget_unreferenced_buckets()
        assert all(h not in bm._buckets for h in orphaned), \
            "released snapshot still pinned its buckets"
    finally:
        app.shutdown()


# --------------------------------------------------------- tx status store --

class _Pair:
    def __init__(self, h, raw):
        class _R:
            def to_bytes(self, _raw=raw):
                return _raw
        self.transactionHash = h
        self.result = _R()


def test_tx_status_store_capacity_and_ttl():
    store = TxStatusStore(capacity=4, ttl_s=100.0)
    store.record_ledger(2, 1000, [_Pair(sha256(b"%d" % i), b"r%d" % i)
                                  for i in range(3)])
    assert len(store) == 3
    assert store.lookup(sha256(b"0")) == (b"r0", 2)
    assert store.lookup(sha256(b"nope")) is None
    # capacity ring: oldest evicted first
    store.record_ledger(3, 1010, [_Pair(sha256(b"%d" % i), b"s%d" % i)
                                  for i in range(3, 6)])
    assert len(store) == 4
    assert store.lookup(sha256(b"0")) is None
    assert store.lookup(sha256(b"5")) == (b"s5", 3)
    # TTL prune: a close far in the future expires everything older
    store.record_ledger(9, 5000, [_Pair(sha256(b"new"), b"n")])
    assert store.lookup(sha256(b"4")) is None
    assert store.lookup(sha256(b"new")) == (b"n", 9)


def test_completion_stream_feeds_tx_status():
    app, gen = _pay_app()
    try:
        captured = []
        app.ledger_manager.completion_hooks.append(
            lambda seq, ct, pairs: captured.extend(
                (bytes(p.transactionHash), seq) for p in pairs))
        gen.generate_payments(4)
        app.manual_close()
        app.ledger_manager.join_completion()
        assert captured, "completion hook never fired"
        for tx_hash, seq in captured:
            res = app.query_service.query_tx_status(tx_hash)
            assert res["found"] and res["ledger_seq"] == seq
            assert res["result_xdr"]
        missing = app.query_service.query_tx_status(sha256(b"ghost"))
        assert missing["found"] is False
    finally:
        app.shutdown()


# -------------------------------------------------- admission / deadlines --

def test_queue_full_sheds_at_the_door():
    app = _app()
    try:
        svc = app.query_service
        svc.queue_limit = 0          # every admission sees a full queue
        res = svc.query_account(sha256(b"x"))
        assert res["shed"] == "queue-full" and res["found"] is False
        assert svc.shed_counters["queue-full"].count == 1
    finally:
        app.shutdown()


def test_controller_shed_rejects_reads():
    app = _app()
    try:
        app.controller.shed_read = 1.0   # always-drop read admission
        res = app.query_service.query_account(sha256(b"x"))
        assert res["shed"] == "controller"
        assert app.query_service.shed_counters["controller"].count == 1
        assert app.controller.status()["shed"]["read_dropped"] >= 1
    finally:
        app.shutdown()


def test_expired_deadline_resolves_as_timeout():
    app = _app()
    try:
        res = app.query_service.query_account(
            sha256(b"x"), deadline_ms=-50.0)
        assert res.get("timeout") is True and res["found"] is False
        assert app.query_service.timeout_counter.count >= 1
    finally:
        app.shutdown()


def test_slow_lookup_triggers_hedge():
    app = _app()
    try:
        svc = app.query_service
        svc.hedge_min_ms = 1.0
        real = app.snapshots

        class _SlowSnap:
            def __init__(self, snap):
                self._snap = snap
                self.ledger_seq = snap.ledger_seq

            def read_entry(self, key):
                time.sleep(0.03)
                return self._snap.read_entry(key)

        class _SlowSnaps:
            def acquire(self):
                return _SlowSnap(real.acquire())

            def release(self, s):
                real.release(s._snap)

        svc._snapshots = _SlowSnaps()
        res = svc.query_account(sha256(b"x"))
        assert res["ledger_seq"] is not None
        assert svc.hedge_counters["issued"].count >= 1
        # the losing leg is still in flight when the caller returns;
        # give it a beat to land in won/wasted
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and \
                svc.hedge_counters["won"].count + \
                svc.hedge_counters["wasted"].count < 1:
            time.sleep(0.01)
        assert svc.hedge_counters["won"].count + \
            svc.hedge_counters["wasted"].count >= 1
    finally:
        app.shutdown()


def test_batch_read_answers_from_one_snapshot():
    app = _app()
    try:
        seed_accounts_bulk(app, 50)
        ids = [bulk_account_id(i) for i in (0, 7, 49)] + \
            [sha256(b"absent")]
        res = app.query_service.query_accounts(ids)
        assert res["found"] is True
        assert res["ledger_seq"] == \
            app.ledger_manager.get_last_closed_ledger_num()
        entries = res["entries_xdr"]
        assert len(entries) == 4
        assert all(e is not None for e in entries[:3])
        assert entries[3] is None
    finally:
        app.shutdown()


# ----------------------------------------------------------- shed ladder --

def _query_sample(t, read_p99, close_p99=100.0):
    return {
        "t": float(t), "ledger": int(t), "pending_txs": 0,
        "tx_applied": 0,
        "close": {"count": 5, "median_ms": close_p99 / 2,
                  "p99_ms": close_p99, "max_ms": close_p99},
        "tx_e2e": {"count": 0},
        "query": {"count": 50, "p50_ms": read_p99 / 2,
                  "p99_ms": read_p99, "queue": 0,
                  "p95_estimate_ms": read_p99, "shed": {},
                  "hedge": {}, "timeouts": 0, "snapshots": {}},
        "verify": None, "breaker": None, "breaker_open": 0.0,
        "flood": None, "dispatch": None, "mesh": None,
        "host": {"load1": 0.0, "ncpu": 1},
    }


def test_read_breach_sheds_reads_before_writes():
    app = _app()
    try:
        ctl = app.controller
        # read p99 breaching hard (SLO_READ_P99_MS=100), close healthy
        for t in (1.0, 2.0, 3.0):
            s = _query_sample(t, read_p99=500.0)
            app.slo.observe(s)
            ctl.tick(s)
        assert ctl.shed_read > 0.0, "read ladder never ramped"
        assert ctl.shed_tx == 0.0 and ctl.shed_flood == 0.0, \
            "write ladders moved on a read-only breach"
        # reads actually dropped at the admission door now
        dropped = sum(ctl.roll_read_shed() for _ in range(300))
        assert dropped > 0
        # recovery decays the ladder back down
        peak = ctl.shed_read
        for t in range(4, 24):
            s = _query_sample(float(t), read_p99=1.0)
            app.slo.observe(s)
            ctl.tick(s)
        assert ctl.shed_read < peak
        assert ctl.shed_read < 0.1
    finally:
        app.shutdown()


def test_write_pressure_sheds_reads_faster_than_writes():
    app = _app()
    try:
        ctl = app.controller
        s = _query_sample(1.0, read_p99=1.0, close_p99=10_000.0)
        app.slo.observe(s)
        ctl.tick(s)
        # close breach: reads shed at 2x the write ramp (sacrificial)
        assert ctl.shed_read > ctl.shed_tx > 0.0
    finally:
        app.shutdown()


# -------------------------------------------------------- seeding / index --

def test_bulk_seeding_serves_reads_and_survives_closes():
    app, gen = _pay_app()
    try:
        seed_accounts_bulk(app, 200)
        res = app.query_service.query_account(bulk_account_id(123))
        assert res["found"], "seeded account unreadable"
        # the seeded list still closes ledgers (hash recomputed over
        # the seeded levels) and the account stays readable after
        gen.generate_payments(4)
        app.manual_close()
        res2 = app.query_service.query_account(bulk_account_id(123))
        assert res2["found"]
        assert res2["ledger_seq"] == res["ledger_seq"] + 1
        assert res2["entry_xdr"] == res["entry_xdr"]
    finally:
        app.shutdown()


def test_bucket_index_meters_drain_into_registry():
    app = _app()
    try:
        seed_accounts_bulk(app, 100)
        svc = app.query_service
        for i in range(20):
            svc.query_account(bulk_account_id(i))
        svc.query_account(sha256(b"not-seeded"))
        rep = app.bucket_manager.drain_index_meters(
            app.metrics,
            extra_buckets=app.snapshots.live_buckets())
        assert rep["lookups"] > 0 and rep["hit"] >= 20
        assert app.metrics.meter("bucket", "index", "hit").count >= 20
        # second drain starts from zero (take_stats resets)
        rep2 = app.bucket_manager.drain_index_meters(
            app.metrics,
            extra_buckets=app.snapshots.live_buckets())
        assert rep2["lookups"] == 0
    finally:
        app.shutdown()


# ---------------------------------------------------------------- routes --

def test_http_routes_answer_reads():
    app, gen = _pay_app()
    try:
        raw = gen.accounts[0].key.public_key().raw
        out = app.command_handler.handle(
            "account", {"id": StrKey.encode_ed25519_public(raw)})
        assert out["found"] and out["ledger_seq"] == \
            app.ledger_manager.get_last_closed_ledger_num()
        assert out["entry"]                       # base64 entry XDR
        out_hex = app.command_handler.handle(
            "account", {"id": raw.hex()})
        assert out_hex["entry"] == out["entry"]
        gen.generate_payments(4)
        app.manual_close()
        app.ledger_manager.join_completion()
        captured = []
        app.ledger_manager.completion_hooks.append(
            lambda seq, ct, pairs: captured.extend(pairs))
        gen.generate_payments(2)
        app.manual_close()
        app.ledger_manager.join_completion()
        tx_hash = bytes(captured[0].transactionHash)
        st = app.command_handler.handle(
            "txstatus", {"hash": tx_hash.hex()})
        assert st["found"] and st["result"]
        info = app.command_handler.handle("snapshotinfo", {})
        assert info["snapshot"]["ledger_seq"] == \
            app.ledger_manager.get_last_closed_ledger_num()
        assert info["pinned_buckets"] >= 1
        assert info["tx_status_entries"] >= 2
    finally:
        app.shutdown()


def test_concurrent_readers_against_closing_ledgers():
    """Four reader threads hammer the pool while the main thread
    closes ledgers — every response seq must name a closed ledger and
    nothing deadlocks."""
    app, gen = _pay_app()
    try:
        seed_accounts_bulk(app, 100)
        lock = threading.Lock()
        closed = {app.ledger_manager.get_last_closed_ledger_num()}

        def rec(h, _):
            with lock:
                closed.add(h.ledgerSeq)
        app.ledger_manager.closed_hooks.insert(0, rec)
        bad, done = [], threading.Event()

        def reader(k):
            i = 0
            while not done.is_set():
                res = app.query_service.query_accounts(
                    [bulk_account_id((k * 31 + i + j) % 100)
                     for j in range(4)])
                i += 1
                if res.get("shed") or res.get("timeout"):
                    continue
                with lock:
                    if res["ledger_seq"] not in closed:
                        bad.append(res["ledger_seq"])
        ts = [threading.Thread(target=reader, args=(k,), daemon=True)
              for k in range(4)]
        for t in ts:
            t.start()
        for _ in range(4):
            gen.generate_payments(4)
            app.manual_close()
            gen.sync_account_seqs()
        done.set()
        for t in ts:
            t.join(timeout=10.0)
        assert not bad, f"responses named unclosed seqs: {bad[:5]}"
    finally:
        app.shutdown()
