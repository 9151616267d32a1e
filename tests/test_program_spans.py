"""Spans and counters inside the program (ISSUE 25): node lifecycle and
catchup stages, the herder's own time, host signature verifies, the
life of a device batch, JAX's trace/lower/compile — that each appears,
that the ids join across threads, and that nothing of it sits on a
per-transaction or per-signature path while no trace is on.

Two nodes are run once for the whole module: a standalone node that
closes one checkpoint of ledgers (debug meta on, so ledger 63
compresses a segment) and publishes it, and fresh nodes that catch up
from its archive with a `TpuBatchVerifier` whose kernel is a stand-in
(all verdicts true, nothing traced or compiled)."""

import gc
import threading
import time

import numpy as np
import pytest

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.crypto import keys
from stellar_core_tpu.crypto.keys import SecretKey, clear_verify_cache
from stellar_core_tpu.history import make_tmpdir_archive
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops.verifier import TpuBatchVerifier
from stellar_core_tpu.tx import signature_checker
from stellar_core_tpu.util import jax_cache, perf, tracing
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.perf import ZoneRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State, run_work_to_completion

import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment

CHECKPOINT = 63


@pytest.fixture(autouse=True)
def _no_leftover_tracing():
    yield
    with tracing._state_lock:
        del tracing._active[:]
        tracing.ENABLED = False
        if tracing._on_gc in gc.callbacks:
            gc.callbacks.remove(tracing._on_gc)


def _events(app) -> list:
    return app.flight_recorder.to_chrome_trace()["traceEvents"]


def _seen(app) -> dict:
    """What a benchmark reader is handed: zones and metrics by name,
    with their counts."""
    out = {name: z["count"] for name, z in app.perf.report().items()}
    out.update({name: m.get("count", 0)
                for name, m in app.metrics.to_json().items()})
    return out


class _StandInKernel(TpuBatchVerifier):
    """The real pack / enqueue / collect path round a kernel that says
    true for every lane; `gate`, when given, holds the collect back
    until it is set (a batch that lands late), and `fail` makes the
    collect raise (a device lost after the dispatch)."""

    def __init__(self, app, gate=None, fail=False):
        super().__init__(perf=app.perf, metrics=app.metrics,
                         device_min_batch=1)

        def kernel(pubs, *rest):
            out = np.ones(len(pubs), dtype=bool)
            if gate is None and not fail:
                return out

            class Late:
                def __array__(self, *a, **kw):
                    if fail:
                        raise RuntimeError("device lost")
                    gate.wait(30)
                    return out
            return Late()
        self._jit = self._jit_msg32 = kernel


def _publisher(tmp_path):
    """A traced standalone node: 5 accounts, a payment each in ledgers
    3..5, then empty closes up to the checkpoint, which it publishes."""
    root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.HISTORY = {"test": {
        "get": f"cp {root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {root}/{{1}}) && cp {{0}} {root}/{{1}}"}}
    cfg.METADATA_DEBUG_LEDGERS = 64
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.flight_recorder.start()
    master = m1.master_account(app)
    dests = [m1.AppAccount(app, SecretKey.from_seed(bytes([i]) * 32))
             for i in range(1, 6)]
    for d in dests:
        m1.submit(app, master.tx([op_create_account(d.account_id, 10**12)]))
    app.manual_close()
    for d in dests:
        d.sync_seq()
    for _ in range(3):
        for d in dests:
            m1.submit(app, d.tx([op_payment(master.muxed, 1000)]))
        app.manual_close()
    while app.ledger_manager.get_last_closed_ledger_num() < CHECKPOINT:
        app.manual_close()
    # the checkpoint's publish rides its ledger's tail: who reads the
    # archive joins first
    app.herder.join_completion()
    return app, make_tmpdir_archive("test", root)


def _catchup(publisher, archive, gate=None, fail=False):
    """A traced fresh node that replays the checkpoint; returns it (not
    yet shut down), its catchup work and the checks its applies asked
    of a `PrevalidatedVerifier`."""
    cfg = get_test_config()
    cfg.NETWORK_PASSPHRASE = publisher.config.NETWORK_PASSPHRASE
    clear_verify_cache()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.flight_recorder.start()
    verifier = _StandInKernel(app, gate, fail)
    asked = []
    real_call = signature_checker.PrevalidatedVerifier.__call__

    def counting_call(self, pub, sig, msg):
        asked.append(1)
        return real_call(self, pub, sig, msg)
    signature_checker.PrevalidatedVerifier.__call__ = counting_call
    try:
        # a batch on time is adopted at the first apply (the grace
        # covers the hop to the collect thread); a late one never is
        work = CatchupWork(app, archive, CatchupConfiguration(to_ledger=0),
                           batch_verifier=verifier,
                           batch_grace=0.0 if gate else 60.0)
        state = run_work_to_completion(app, work, timeout_virtual=3000)
        if gate is not None:
            gate.set()
        work.drain(30.0)
    finally:
        signature_checker.PrevalidatedVerifier.__call__ = real_call
    assert state == State.WORK_SUCCESS
    assert app.ledger_manager.get_last_closed_ledger_num() == CHECKPOINT
    assert app.ledger_manager.get_last_closed_ledger_hash() == \
        publisher.ledger_manager.get_last_closed_ledger_hash()
    return app, work, len(asked)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    publisher, archive = _publisher(tmp)
    out = {}
    try:
        out["standalone"] = _seen(publisher)
        on_time, _, asked = _catchup(publisher, archive)
        out["catchup"] = _seen(on_time)
        out["catchup_asked"] = asked
        out["catchup_events"] = _events(on_time)
        on_time.shutdown()
        late, _, asked = _catchup(publisher, archive, threading.Event())
        out["late"] = _seen(late)
        out["late_asked"] = asked
        late.shutdown()
        failed, work, asked = _catchup(publisher, archive, fail=True)
        out["failed"] = _seen(failed)
        out["failed_asked"] = asked
        out["failed_tables"] = [cw.prevalidated
                                for cw in work.applied_checkpoints]
        failed.shutdown()
    finally:
        publisher.shutdown()
    out["standalone_events"] = _events(publisher)
    out["standalone_tracing_after"] = tracing.ENABLED
    return out


# ------------------------------------- every new zone and counter shows --

@pytest.mark.parametrize("run,name", [
    # A. node lifecycle, catchup stages, the checkpoint's compression
    ("standalone", "app.create"),
    ("standalone", "app.start"),
    ("standalone", "ledger.close.meta.compress"),
    ("catchup", "app.create"),
    ("catchup", "app.start"),
    ("catchup", "catchup.download.wall"),
    # B. herder
    ("standalone", "herder.recvTransaction"),
    ("standalone", "herder.triggerNextLedger"),
    ("standalone", "herder.trimInvalid"),
    ("standalone", "herder.trim.verdict.hit"),
    ("standalone", "herder.makeTxSet"),
    ("standalone", "herder.ledgerClosed"),
    ("standalone", "herder.joinCompletion"),
    # C. signatures on the host, and what the batch was good for
    ("standalone", "crypto.verify.native"),
    ("standalone", "crypto.verify.cache.hit"),
    ("standalone", "crypto.verify.cache.miss"),
    ("catchup", "crypto.prevalidated.hit"),
    ("catchup", "catchup.batch.adoptLag"),
    ("late", "crypto.prevalidated.miss"),
    ("late", "crypto.verify.native"),
    # D. the life of a device batch
    ("catchup", "crypto.batchVerify"),
    ("catchup", "crypto.batchVerify.pack"),
    ("catchup", "crypto.batchVerify.enqueue"),
    ("catchup", "crypto.batchVerify.collect"),
    ("catchup", "crypto.verify.dispatch.host"),
])
def test_new_zone_or_counter_is_counted(runs, run, name):
    assert runs[run].get(name, 0) > 0, sorted(runs[run])


def test_recv_transaction_zone_counts_every_call(runs):
    # 5 account creations + 3 ledgers x 5 payments
    assert runs["standalone"]["herder.recvTransaction"] == 20
    assert runs["standalone"]["herder.triggerNextLedger"] == CHECKPOINT - 1


def test_trim_verdict_counters_sit_beside_the_trim_zone(runs):
    # ISSUE 42: every transaction of the rehearsal is admitted and
    # trimmed at one LCL (the five creations are one account's chain),
    # so each is kept on its verdict; the miss counter is there and 0
    seen = runs["standalone"]
    assert seen["herder.trim.verdict.hit"] == 20
    assert seen["herder.trim.verdict.miss"] == 0
    assert seen["herder.trimInvalid"] == CHECKPOINT - 1
    spans = [ev for ev in runs["standalone_events"]
             if ev["ph"] == "B" and ev["name"] == "herder.trimInvalid"]
    assert [ev["args"]["seq"] for ev in spans] == \
        list(range(2, CHECKPOINT + 1))


@pytest.mark.parametrize("run", ["standalone", "catchup"])
def test_close_read_counters_of_a_payment_node(runs, run):
    # ISSUE 43: the root's point reads are a counter from the ledger
    # manager's start, whatever it reads; a node that applies no
    # Soroban operation builds no configuration and publishes none
    seen = runs[run]
    assert "ledger.root.point.sql" in seen
    assert "soroban.config.load" not in seen
    assert "soroban.invoke" not in seen


def test_prevalidated_counts_are_the_checks_apply_made(runs):
    seen = runs["catchup"]
    assert runs["catchup_asked"] > 0
    assert seen["crypto.prevalidated.hit"] + \
        seen.get("crypto.prevalidated.miss", 0) == runs["catchup_asked"]
    # single-signer transactions and a batch adopted before the first
    # apply: the table answers every check, the host verifies nothing
    assert seen.get("crypto.prevalidated.miss", 0) == 0


def test_a_batch_that_lands_late_gives_misses(runs):
    seen = runs["late"]
    assert seen["crypto.prevalidated.miss"] == runs["late_asked"] > 0
    assert seen.get("crypto.prevalidated.hit", 0) == 0
    assert seen.get("catchup.batch.adoptLag", 0) == 0


def test_a_batch_that_fails_takes_its_empty_table_away(runs):
    """A collect that raises: the table is published and dropped, so
    the rest of the checkpoint's checks go straight to the verifier
    and pay no key and no miss."""
    assert runs["failed_tables"] == [None]
    assert runs["failed_asked"] == 0
    seen = runs["failed"]
    assert "crypto.prevalidated.hit" in seen
    assert seen["crypto.prevalidated.hit"] == 0
    assert seen["crypto.prevalidated.miss"] == 0
    assert seen["crypto.verify.native"] > 0


def test_one_batch_id_from_pack_to_adoption_across_threads(runs):
    spans = {}
    for ev in runs["catchup_events"]:
        if ev["ph"] in ("B", "i") and (
                ev["name"].startswith("crypto.batchVerify.")
                or ev["name"] == "catchup.batch.adopted"):
            spans[ev["name"]] = ev
    assert set(spans) == {"crypto.batchVerify.pack",
                          "crypto.batchVerify.enqueue",
                          "crypto.batchVerify.collect",
                          "catchup.batch.adopted"}
    assert {ev["args"]["batch"] for ev in spans.values()} == {1}
    pack, collect = (spans["crypto.batchVerify.pack"],
                     spans["crypto.batchVerify.collect"])
    assert pack["tid"] == spans["catchup.batch.adopted"]["tid"]
    assert collect["tid"] != pack["tid"]
    assert pack["args"]["n"] == collect["args"]["n"] == \
        spans["catchup.batch.adopted"]["args"]["n"]
    assert collect["args"]["bucket"] >= collect["args"]["n"]
    adopted = spans["catchup.batch.adopted"]["args"]
    assert adopted["checkpoint"] == CHECKPOINT and adopted["seq"] == 2


def test_download_is_an_async_span_with_an_exit_code(runs):
    begun = {ev["id"] for ev in runs["catchup_events"]
             if ev["ph"] == "b" and ev["name"] == "catchup.download"}
    ended = {ev["id"]: ev["args"] for ev in runs["catchup_events"]
             if ev["ph"] == "e" and ev["name"] == "catchup.download"}
    assert begun and begun == set(ended)
    assert all(a["exit"] == 0 for a in ended.values())
    assert not [ev for ev in runs["catchup_events"]
                if ev["ph"] == "i" and ev["name"] == "catchup.download"]
    assert len(begun) == runs["catchup"]["catchup.download.wall"]


def test_recording_runs_to_the_end_of_shutdown(runs):
    """`shutdown()` stops the recorder last: the completion tail it
    waits for is in the recording, and the process-wide guard is
    released."""
    names = [(ev["ph"], ev["name"]) for ev in runs["standalone_events"]
             if ev["name"].startswith("app.shutdown")]
    assert names == [("B", "app.shutdown"),
                     ("B", "app.shutdown.joinCompletion"),
                     ("E", "app.shutdown.joinCompletion"),
                     ("E", "app.shutdown")]
    assert runs["standalone_tracing_after"] is False


def test_shutdown_that_raises_releases_the_recorder():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    app.flight_recorder.start()
    real = app.herder.shutdown

    def boom():
        raise RuntimeError("boom")
    app.herder.shutdown = boom
    with pytest.raises(RuntimeError):
        app.shutdown()
    assert not app.flight_recorder.active and tracing.ENABLED is False
    app.herder.shutdown = real
    app.shutdown()


def test_spans_under_a_close_carry_its_seq(runs):
    want = {"herder.triggerNextLedger", "herder.trimInvalid",
            "herder.makeTxSet", "herder.ledgerClosed",
            "herder.joinCompletion", "ledger.close.meta.compress"}
    seqs = {}
    for ev in runs["standalone_events"]:
        if ev["ph"] == "B" and ev["name"] in want:
            seqs.setdefault(ev["name"], []).append(ev["args"]["seq"])
    assert set(seqs) == want
    assert seqs["ledger.close.meta.compress"] == [CHECKPOINT]
    assert seqs["herder.trimInvalid"] == seqs["herder.triggerNextLedger"]
    # no close joins its own tail: the one join is the archive reader's
    assert seqs["herder.joinCompletion"] == [CHECKPOINT]


# --------------------------------------------------- the registry itself --

def test_zone_registry_add_and_zone_agree():
    a, b = ZoneRegistry(), ZoneRegistry()
    for _ in range(3):
        with a.zone("z"):
            time.sleep(0.002)
    ra = a.report()["z"]
    b.add("z", ra["total_ms"] / 1e3, count=3)
    rb = b.report()["z"]
    assert rb["count"] == ra["count"] == 3
    assert rb["total_ms"] == pytest.approx(ra["total_ms"], abs=1e-3)
    assert rb["mean_ms"] == pytest.approx(ra["mean_ms"], abs=1e-3)
    assert 0 < rb["max_ms"] <= ra["max_ms"]
    b.add("z", 1.0, count=0)            # nothing measured: no entry moves
    assert b.report()["z"] == rb
    b.add("single", 0.5)
    assert b.report()["single"] == {"count": 1, "total_ms": 500.0,
                                    "mean_ms": 500.0, "max_ms": 500.0}


def test_publish_verify_counts_drains_once():
    clear_verify_cache()
    keys.publish_verify_counts(MetricsRegistry(), ZoneRegistry())  # zero
    sk = SecretKey.from_seed(b"\x07" * 32)
    sig = sk.sign(b"m" * 32)
    pub = sk.public_key().raw
    assert keys.PubKeyUtils.verify_sig(pub, sig, b"m" * 32)    # miss
    assert keys.PubKeyUtils.verify_sig(pub, sig, b"m" * 32)    # hit
    assert keys.verify_sig_uncached(pub, sig, b"m" * 32)
    first, second = MetricsRegistry(), MetricsRegistry()
    zones1, zones2 = ZoneRegistry(), ZoneRegistry()
    keys.publish_verify_counts(first, zones1)
    keys.publish_verify_counts(second, zones2)
    native = zones1.report()["crypto.verify.native"]
    assert native["count"] == 2 and native["total_ms"] > 0
    doc = first.to_json()
    assert doc["crypto.verify.cache.hit"]["count"] == 1
    assert doc["crypto.verify.cache.miss"]["count"] == 1
    # the second reader finds the meters' families, and nothing in them
    assert zones2.report() == {}
    assert {n: m["count"] for n, m in second.to_json().items()} == {
        "crypto.verify.cache.hit": 0, "crypto.verify.cache.miss": 0}


def test_metrics_route_shows_verify_counts_and_jax_zones():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        perf.default_registry.add("jax.trace", 0.25)
        keys.verify_sig_uncached(b"\x01" * 32, b"\x02" * 64, b"m")
        out = app.command_handler.handle("metrics", {})
        assert out["perf_zones"]["crypto.verify.native"]["count"] >= 1
        assert "app.start" in out["perf_zones"]
        # JAX's zones belong to the process: a key of their own
        assert out["process_zones"]["jax.trace"]["count"] >= 1
        assert "jax.trace" not in out["perf_zones"]
        shown = app.command_handler.handle("perf", {})
        assert "jax.trace" in shown["process_zones"]
        assert "jax.trace" not in shown["perf"]
        text = app.command_handler.handle(
            "metrics", {"format": "prometheus"})["_raw_body"]
        assert 'process_zone_count{zone="jax.trace"}' in text
        assert 'perf_zone_count{zone="jax.trace"}' not in text
        assert 'perf_zone_count{zone="crypto.verify.native"}' in text
    finally:
        app.shutdown()


def test_clearmetrics_empties_the_nodes_zones_after_a_compile():
    """`clearmetrics` and `perf?reset=1` empty `perf_zones` whatever the
    process has compiled or verified before; the process-wide zones
    stay, under their own key."""
    import jax
    jax_cache.watch_jax_compiles()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        h = app.command_handler
        assert float(jax.jit(lambda x: x * 5 + 2)(1.0)) == 7.0
        keys.verify_sig_uncached(b"\x01" * 32, b"\x02" * 64, b"m")
        assert h.handle("clearmetrics")["status"] == "ok"
        out = h.handle("metrics")
        assert out["perf_zones"] == {}
        assert out["process_zones"]["jax.backendCompile"]["count"] >= 1
        app.manual_close()
        assert h.handle("perf", {"reset": "1"})["perf"]
        shown = h.handle("perf")
        assert shown["perf"] == {}
        assert "jax.trace" in shown["process_zones"]
    finally:
        app.shutdown()


# -------------------------------------------------- E. JAX's own work ----

def test_jax_trace_lower_compile_reach_the_default_registry():
    import jax
    jax_cache.watch_jax_compiles()
    jax_cache.watch_jax_compiles()          # registers once
    before = perf.default_registry.report()
    rec = tracing.FlightRecorder()
    rec.start()
    try:
        def issue25_probe(x):
            return x * 3 + 1
        assert float(jax.jit(issue25_probe)(2.0)) == 7.0
    finally:
        rec.stop()
    after = perf.default_registry.report()
    for zone in ("jax.trace", "jax.lower", "jax.backendCompile"):
        # once each: the `jnp` calls traced inside the probe are
        # events of their own, nested in the probe's, and not counted
        assert after[zone]["count"] == \
            before.get(zone, {"count": 0})["count"] + 1, zone
    # the instants name the function that stalled the caller
    stages = {ev["args"]["stage"]: ev["args"]
              for ev in rec.to_chrome_trace()["traceEvents"]
              if ev["name"] == "jax.compile"
              and "issue25_probe" in ev["args"]["fun"]}
    assert set(stages) == {"trace", "lower", "backendCompile"}
    assert all(a["seconds"] >= 0 for a in stages.values())
    # persistent-cache lookups are counted as zones of 0 seconds
    hits = after.get("jax.compileCache.hit", {"count": 0})["count"]
    jax_cache._on_event("/jax/compilation_cache/cache_hits")
    jax_cache._on_event("/jax/some/other/event")
    assert perf.default_registry.report()["jax.compileCache.hit"] == {
        "count": hits + 1, "total_ms": 0.0, "mean_ms": 0.0, "max_ms": 0.0}


# ------------------------- nothing new on the per-item paths, trace off --

NEW_ZONES = {"crypto.verify.native", "app.create", "app.start", "app.shutdown",
             "app.shutdown.joinCompletion", "ledger.close.meta.compress",
             "herder.recvTransaction", "herder.triggerNextLedger",
             "herder.trimInvalid", "herder.makeTxSet", "herder.ledgerClosed",
             "herder.joinCompletion", "crypto.batchVerify.pack",
             "crypto.batchVerify.enqueue", "crypto.batchVerify.collect",
             "jax.trace", "jax.lower", "jax.backendCompile"}
NEW_METRICS = ("crypto.prevalidated.hit", "crypto.prevalidated.miss",
               "crypto.verify.dispatch.host", "catchup.download.wall",
               "catchup.batch.adoptLag")


@pytest.fixture
def tripwired_app(monkeypatch):
    """A started node on which every registry entry point of the new
    instrumentation raises: `ZoneRegistry.zone` / `add` for the new
    names, `inc` / `update` of the new metrics, and the thread clock
    (ISSUE 37: `recv_transaction` reads it only while a recorder
    records, and then once a close; `verify_sig_uncached` never; a
    zone reads it always, so no zone is on these paths either)."""
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    real_zone, real_add = ZoneRegistry.zone, ZoneRegistry.add

    def zone(self, name, targs=None, sink=None):
        assert name not in NEW_ZONES, name
        return real_zone(self, name, targs, sink)

    def add(self, name, seconds, count=1, cpu_seconds=None):
        assert name not in NEW_ZONES, name
        return real_add(self, name, seconds, count, cpu_seconds)
    monkeypatch.setattr(ZoneRegistry, "zone", zone)
    monkeypatch.setattr(ZoneRegistry, "add", add)

    def trip(*a, **kw):
        raise AssertionError("a new metric was touched on a per-item path")
    for name in NEW_METRICS:
        metric = app.metrics.new_counter(name) if "prevalidated" in name \
            else app.metrics.new_timer(name)
        for entry in ("inc", "update"):
            if hasattr(metric, entry):
                setattr(metric, entry, trip)
    yield app
    monkeypatch.undo()
    app.shutdown()


def _payment(app):
    master = m1.master_account(app)
    return master.tx([op_payment(master.muxed, 1)])


@pytest.mark.parametrize("path", ["recv_transaction", "verify_sig",
                                  "PrevalidatedVerifier.__call__"])
def test_per_item_paths_reach_no_new_registry_entry_point(tripwired_app,
                                                          path,
                                                          monkeypatch):
    app = tripwired_app
    assert tracing.ENABLED is False
    frame = _payment(app)
    pub, sig, msg = signature_checker.collect_signature_tuples([frame])[0]
    clear_verify_cache()

    def thread_clock():
        raise AssertionError("the thread clock was read on a per-item "
                             "path with no recorder active")
    monkeypatch.setattr(time, "thread_time", thread_clock)
    if path == "recv_transaction":
        from stellar_core_tpu.herder.tx_queue import AddResult
        assert app.herder.recv_transaction(frame) == \
            AddResult.ADD_STATUS_PENDING
        assert app.herder._recv_count == 1 and app.herder._recv_seconds > 0
        assert app.herder._recv_run is None
    elif path == "verify_sig":
        assert keys.PubKeyUtils.verify_sig(pub, sig, msg)        # miss
        assert keys.PubKeyUtils.verify_sig(pub, sig, msg)        # hit
        assert keys._native_count >= 1
    else:
        pv = signature_checker.PrevalidatedVerifier()
        pv.add_results([(pub, sig, msg)], [True])
        assert pv(pub, sig, msg) and pv(pub, sig, b"x" * 32) is False
        assert (pv.hits, pv.misses) == (1, 1)
    # ... and the once-a-close publication is where they are reached
    with pytest.raises(AssertionError):
        app.manual_close()
