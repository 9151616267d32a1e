"""Flight-recorder tests (ISSUE 3): span tracing, Chrome-trace export,
Prometheus exposition, tx end-to-end latency, and the observability
satellites (clearmetrics+zones, per-peer counters, Meter EWMA windows,
the tracing-disabled cost contract)."""

import gc
import json
import re
import threading
import time
import tracemalloc

import pytest

from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.simulation import LoadGenerator, topologies
from stellar_core_tpu.util import tracing
from stellar_core_tpu.util.metrics import (Meter, MetricsRegistry,
                                           render_prometheus)
from stellar_core_tpu.util.perf import ZoneRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_overlay as ovl


@pytest.fixture(autouse=True)
def _no_leftover_tracing():
    """Every test starts and ends with tracing disabled (a leaked
    active recorder would make every other test pay for spans)."""
    yield
    with tracing._state_lock:
        del tracing._active[:]
        tracing.ENABLED = False
        if tracing._on_gc in gc.callbacks:
            gc.callbacks.remove(tracing._on_gc)


# ------------------------------------------------------------ recorder --

def test_enabled_refcounts_across_recorders():
    a, b = tracing.FlightRecorder(), tracing.FlightRecorder()
    assert tracing.ENABLED is False
    a.start()
    b.start()
    assert tracing.ENABLED
    a.stop()
    assert tracing.ENABLED          # b still recording
    b.stop()
    assert tracing.ENABLED is False
    # double stop is a no-op, not an underflow
    b.stop()
    a.start()
    assert tracing.ENABLED
    a.stop()
    assert tracing.ENABLED is False


def test_disabled_path_is_one_constant_check_no_alloc():
    """The cost contract (mirrors chaos.ENABLED): with no recorder
    active, an instrumented span site runs one module-constant check —
    no recorder call, no event, no allocation attributable to the
    tracing module."""
    assert tracing.ENABLED is False
    rec = tracing.FlightRecorder()
    reg = ZoneRegistry()
    reg.tracer = rec

    def span_site():
        # the exact guard pattern every instrumented hot path uses
        if tracing.ENABLED:
            rec.begin("x")
            rec.end("x")

    span_site()                       # warm anything lazy
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(2000):
        span_site()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(
        st.size_diff for st in after.compare_to(before, "filename")
        if st.traceback[0].filename == tracing.__file__)
    assert grown == 0, "tracing-disabled span site allocated memory"
    assert len(rec) == 0 and rec._appended == 0
    # the zone path records nothing either (and aggregates as before)
    with reg.zone("z"):
        pass
    assert len(rec) == 0
    assert reg.report()["z"]["count"] == 1
    # ... and what only a recording pays for is not there: no entry of
    # the collector's callbacks, no read of the thread clock round a
    # host verify (a zone reads it always; the per-signature site
    # never, the per-transaction one under the constant and once a
    # close: tests/test_zone_cpu.py)
    assert tracing._on_gc not in gc.callbacks
    from stellar_core_tpu.crypto import keys
    sk = keys.SecretKey.from_seed(b"\x09" * 32)
    msg = b"d" * 32
    sig = sk.sign(msg)
    real = time.thread_time

    def thread_clock():
        raise AssertionError("thread clock read with no recorder active")
    time.thread_time = thread_clock
    try:
        assert keys.verify_sig_uncached(sk.public_key().raw, sig, msg)
    finally:
        time.thread_time = real


def test_zone_routes_spans_into_recorder():
    rec = tracing.FlightRecorder()
    reg = ZoneRegistry()
    reg.tracer = rec
    rec.start()
    try:
        with reg.zone("outer", targs={"seq": 7}):
            with reg.zone("inner"):
                pass
    finally:
        rec.stop()
    doc = rec.to_chrome_trace()
    spans = [e for e in doc["traceEvents"] if e["ph"] in "BE"]
    assert [(e["ph"], e["name"]) for e in spans] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
    assert spans[0]["args"] == {"seq": 7}
    assert spans[0]["tid"] == threading.get_ident()
    # zone aggregation unaffected by the trace ride-along
    assert reg.report()["outer"]["count"] == 1


def test_ring_buffer_bounds_and_reconciliation():
    rec = tracing.FlightRecorder(capacity=8)
    rec.start()
    for i in range(20):
        rec.begin("span-%d" % i)
        rec.end("span-%d" % i)
    rec.stop()
    assert rec.dropped == 32
    events = rec.to_chrome_trace()["traceEvents"]
    # eviction can orphan an E whose B was overwritten; the dump must
    # still emit only matched pairs
    assert sum(1 for e in events if e["ph"] == "B") == \
        sum(1 for e in events if e["ph"] == "E")


def test_unclosed_span_is_closed_at_dump():
    rec = tracing.FlightRecorder()
    rec.start()
    rec.begin("open-forever", {"seq": 1})
    rec.instant("tick")
    rec.stop()
    events = rec.to_chrome_trace()["traceEvents"]
    bs = [e for e in events if e["ph"] == "B"]
    es = [e for e in events if e["ph"] == "E"]
    assert len(bs) == len(es) == 1
    assert es[0]["name"] == "open-forever"
    assert es[0]["ts"] >= bs[0]["ts"]


def test_async_track_correlates_by_id():
    rec = tracing.FlightRecorder()
    rec.start()
    rec.async_begin("tx.e2e", "cafe1234")
    rec.async_end("tx.e2e", "cafe1234", {"seq": 3})
    rec.stop()
    ev = [e for e in rec.to_chrome_trace()["traceEvents"]
          if e["ph"] in ("b", "e")]
    assert [e["ph"] for e in ev] == ["b", "e"]
    assert all(e["id"] == "cafe1234" and e["cat"] == "tx" for e in ev)


# ------------------------------------------------- chrome-trace checks --

def _validate_chrome_events(events):
    """Structural validation: JSON round-trips, per-thread matched B/E
    nesting, per-thread non-decreasing timestamps. Returns spans by
    name for further assertions."""
    events = json.loads(json.dumps(events))     # serializable
    last_ts = {}
    stacks = {}
    spans = {}
    for e in events:
        assert {"ph", "name", "pid", "tid"} <= set(e), e
        if e["ph"] == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last_ts.get(key, 0.0), \
            f"timestamps regress on {key}"
        last_ts[key] = e["ts"]
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e)
        elif e["ph"] == "E":
            assert stacks.get(key), f"E with no open B on {key}"
            opened = stacks[key].pop()
            spans.setdefault(opened["name"], []).append(
                (opened, e["ts"] - opened["ts"],
                 len(stacks[key])))        # (begin, dur, depth)
    for key, stack in stacks.items():
        assert not stack, f"unclosed spans in dump on {key}: {stack}"
    return spans


def test_traced_four_node_simulation():
    """Acceptance: a traced 4-node simulation produces Chrome
    trace-event JSON validated structurally — nesting, threads,
    ledger-seq args — plus the tx e2e latency track."""
    sim = topologies.core(4)
    try:
        for a in sim.apps():
            a.flight_recorder.start()
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(2))
        app = sim.apps()[0]
        lg = LoadGenerator(app)
        assert lg.generate_accounts(4) == 4
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        lg.sync_account_seqs()
        assert lg.generate_payments(4) == 4     # 4 distinct e2e tracks
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        assert lg.failed == 0

        doc = app.command_handler.handle("dumptrace")["trace"]
        events = doc["traceEvents"]
        spans = _validate_chrome_events(events)

        # ledger-seq args on the close spans (Tracy zone-value parity)
        closes = spans.get("ledger.closeLedger")
        assert closes, "no closeLedger spans in trace"
        seqs = [c[0]["args"]["seq"] for c in closes]
        assert all(isinstance(s, int) and s >= 2 for s in seqs)

        # nesting: close phases recorded INSIDE closeLedger (depth > 0)
        assert any(depth > 0 for _, _, depth
                   in spans.get("ledger.close.applyTx", [])), \
            "close phases are not nested under closeLedger"

        # threads: every tid that emitted events has thread metadata
        tids = {e["tid"] for e in events if e["ph"] != "M"}
        named = {e["tid"] for e in events
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert tids and tids <= named

        # cross-subsystem spans: overlay + SCP lifecycle all present
        names = {e["name"] for e in events}
        assert "overlay.recv" in names
        assert "overlay.send" in names
        assert "scp.envelope.emit" in names
        assert "herder.recvSCPEnvelope" in names

        # the tx e2e track: async begin/end pairs + the timer samples
        phs = {e["ph"] for e in events if e["name"] == "tx.e2e"}
        assert phs == {"b", "e"}
        e2e = app.metrics.to_json()["ledger.transaction.e2e"]
        assert e2e["count"] >= 4 and e2e["median"] > 0

        # node labels separate the processes in the merged view
        assert app.flight_recorder.label
    finally:
        sim.stop_all_nodes()
    # stop_all_nodes released every recorder refcount
    assert tracing.ENABLED is False


def test_admin_trace_routes_roundtrip():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        h = app.command_handler
        assert "exception" in h.handle("stoptrace")   # nothing recording
        out = h.handle("starttrace", {"capacity": "4096"})
        assert out["status"] == "ok" and out["capacity"] == 4096
        app.manual_close()
        out = h.handle("stoptrace")
        assert out["status"] == "ok" and out["events"] > 0
        doc = h.handle("dumptrace")["trace"]
        spans = _validate_chrome_events(doc["traceEvents"])
        assert "ledger.closeLedger" in spans
        # dump to a file path too
        import tempfile
        path = tempfile.mktemp(suffix=".json")
        out = h.handle("dumptrace", {"path": path})
        assert out["status"] == "ok"
        with open(path) as f:
            assert json.load(f)["traceEvents"]
        # create-only: the route must refuse to truncate existing files
        assert "exception" in h.handle("dumptrace", {"path": path})
        import os
        os.unlink(path)
    finally:
        app.shutdown()


# ------------------------------------------------------------ satellites --

def test_clearmetrics_also_resets_zones():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        h = app.command_handler
        app.manual_close()
        assert h.handle("metrics")["perf_zones"]
        assert h.handle("metrics")["metrics"][
            "ledger.ledger.close"]["count"] >= 1
        assert h.handle("clearmetrics")["status"] == "ok"
        out = h.handle("metrics")
        assert out["perf_zones"] == {}
        # metrics reset IN PLACE: the families survive with zeroed
        # values — subsystems cache metric objects at construction, so
        # deregistering would orphan them (counting, never reported)
        assert out["metrics"]["ledger.ledger.close"]["count"] == 0
        assert out["metrics"]["ledger.transaction.e2e"]["count"] == 0
        # a close after clear counts into the SAME cached timer
        app.manual_close()
        assert h.handle("metrics")["metrics"][
            "ledger.ledger.close"]["count"] == 1
        # perf?reset=1 clears the same registry (symmetry)
        assert h.handle("perf", {"reset": "1"})["perf"]
        assert h.handle("perf")["perf"] == {}
    finally:
        app.shutdown()


def test_meter_exposes_all_ewma_windows_and_ticks_catch_up():
    m = Meter()
    m.mark(100)
    # simulate a 10-minute idle gap: the next read must seed the EWMAs
    # and replay the missed 5 s ticks (capped), not return stale zeros
    m._last_tick -= 600.0
    j = m.to_json()
    assert {"1_min_rate", "5_min_rate", "15_min_rate"} <= set(j)
    # decay order after an idle gap: the short window forgets fastest
    assert j["1_min_rate"] < j["5_min_rate"] < j["15_min_rate"]
    assert j["15_min_rate"] > 0
    assert j["count"] == 100
    # a pathological gap hits the tick cap instead of spinning
    m.mark(1)
    m._last_tick -= 1e6
    assert m.to_json()["1_min_rate"] >= 0.0


def test_peers_route_reports_per_peer_counters_and_drops():
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        out = apps[0].command_handler.handle("peers")
        peers = out["authenticated_peers"]
        one = (peers["inbound"] + peers["outbound"])[0]
        assert one["messages_sent"] > 0 and one["messages_received"] > 0
        assert one["bytes_sent"] > 0 and one["bytes_received"] > 0
        # aggregate overlay.peer.* meters registered and counting
        mets = apps[0].metrics.to_json()
        assert mets["overlay.peer.message.sent"]["count"] > 0
        assert mets["overlay.peer.byte.received"]["count"] > 0
        # drop reasons tallied (keyed on the stable prefix) + counter
        conn.initiator.drop("test reason: detail goes here")
        out = apps[0].command_handler.handle("peers")
        assert out["authenticated_peers"]["drop_reasons"] == {
            "test reason": 1}
        assert apps[0].metrics.to_json()[
            "overlay.peer.drop.test-reason"]["count"] == 1
    finally:
        ovl.shutdown(apps)


# ----------------------------------------------------------- prometheus --

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*='
    r'"[^"]*")*\})?'
    r' -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?$')


def _lint_exposition(text: str) -> None:
    """Prometheus text-format lint: HELP/TYPE precede their family,
    every sample line parses, no family is TYPEd twice, and histogram
    families are well-formed (cumulative non-decreasing buckets, the
    +Inf bucket equal to _count)."""
    seen_types = {}
    buckets = {}          # family -> [(le, value)]
    counts = {}           # family -> _count value
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, fam, mtype = line.split(" ", 3)
            assert fam not in seen_types, f"duplicate TYPE for {fam}"
            assert mtype in ("counter", "gauge", "summary", "histogram")
            seen_types[fam] = mtype
        else:
            assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
            name = line.split("{")[0].split(" ")[0]
            base = re.sub(r"_(count|sum|total|bucket)$", "", name)
            assert name in seen_types or base in seen_types, \
                f"sample {name} has no TYPE"
            if name.endswith("_bucket"):
                assert seen_types.get(base) == "histogram", \
                    f"_bucket sample outside a histogram family: {line!r}"
                le, val = line.split('le="', 1)[1].split('"} ')
                buckets.setdefault(base, []).append(
                    (float("inf") if le == "+Inf" else float(le),
                     float(val)))
            elif name.endswith("_count") and \
                    seen_types.get(base) == "histogram":
                counts[base] = float(line.rsplit(" ", 1)[1])
    for fam, bs in buckets.items():
        les = [le for le, _ in bs]
        vals = [v for _, v in bs]
        assert les == sorted(les), f"{fam}: le bounds out of order"
        assert vals == sorted(vals), f"{fam}: buckets not cumulative"
        assert les[-1] == float("inf"), f"{fam}: missing +Inf bucket"
        assert vals[-1] == counts.get(fam), \
            f"{fam}: +Inf bucket != _count"
    assert seen_types, "empty exposition"


def test_prometheus_exposition_format():
    m = MetricsRegistry()
    m.new_counter("ledger.age.closed").inc(3)
    m.new_meter("scp.envelope.receive").mark(10)
    t = m.new_timer("ledger.transaction.apply")
    t.update(0.25)
    t.update(0.5)
    m.new_histogram("2bad.name$with/chars").update(42.0)
    zones = {"ledger.close.seal": {"count": 2, "total_ms": 10.0,
                                   "mean_ms": 5.0, "max_ms": 7.5}}
    text = render_prometheus(m.to_json(), zones)
    _lint_exposition(text)
    # dotted-name sanitization
    assert "ledger_age_closed 3" in text
    assert "scp_envelope_receive_total 10" in text
    # a leading digit cannot start a metric name
    assert "\n_2bad_name_with_chars" in text
    # timer quantiles as labeled samples, in seconds
    assert 'ledger_transaction_apply_seconds{quantile="0.5"}' in text
    assert 'ledger_transaction_apply_seconds{quantile="0.99"}' in text
    assert "ledger_transaction_apply_seconds_count 2" in text
    # meter rate windows labeled
    assert 'scp_envelope_receive_rate{window="15m"}' in text
    # zones as labeled gauge families
    assert 'perf_zone_total_seconds{zone="ledger.close.seal"} 0.01' \
        in text
    assert 'perf_zone_max_seconds{zone="ledger.close.seal"}' in text


def test_timer_bucket_histogram_exposition():
    """Satellite (ISSUE 8): timers additionally export cumulative
    `_bucket` histogram families — summaries with quantile labels
    cannot be aggregated across nodes, fixed-bound buckets can. The
    summary form stays for back-compat."""
    m = MetricsRegistry()
    t = m.new_timer("ledger.transaction.apply")
    for v in (0.0001, 0.003, 0.003, 0.040, 2.0, 60.0):
        t.update(v)
    text = render_prometheus(m.to_json())
    _lint_exposition(text)
    # summary form survives unchanged
    assert 'ledger_transaction_apply_seconds{quantile="0.5"}' in text
    # cumulative histogram family beside it
    assert "# TYPE ledger_transaction_apply_seconds_hist histogram" \
        in text
    assert 'ledger_transaction_apply_seconds_hist_bucket{le="0.0005"}'\
        ' 1' in text
    assert 'ledger_transaction_apply_seconds_hist_bucket{le="0.005"}'\
        ' 3' in text
    assert 'ledger_transaction_apply_seconds_hist_bucket{le="10"} 5' \
        in text
    # the 60 s sample only lands in +Inf
    assert 'ledger_transaction_apply_seconds_hist_bucket{le="+Inf"} 6'\
        in text
    assert "ledger_transaction_apply_seconds_hist_count 6" in text
    # reset zeroes the buckets with everything else
    t.reset()
    text = render_prometheus(m.to_json())
    _lint_exposition(text)
    assert 'ledger_transaction_apply_seconds_hist_bucket{le="+Inf"} 0'\
        in text


def test_metrics_route_prometheus_format():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        app.manual_close()
        out = app.command_handler.handle("metrics",
                                         {"format": "prometheus"})
        assert "_raw_body" in out
        assert out["_content_type"].startswith("text/plain")
        _lint_exposition(out["_raw_body"])
        # the close pipeline's zones are scrapable
        assert 'perf_zone_count{zone="ledger.closeLedger"}' \
            in out["_raw_body"]
        # e2e timer family present (registered at herder construction)
        assert "ledger_transaction_e2e_seconds" in out["_raw_body"]
    finally:
        app.shutdown()
