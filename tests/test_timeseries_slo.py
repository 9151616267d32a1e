"""Telemetry time-series + SLO watchdog + perf trajectory (ISSUE 10).

Covers the tentpole contracts: the sampler ring stays bounded with
eviction accounting, the `since=` scrape cursor resyncs across
restarts/clears instead of silently gapping, SLO verdicts are
deterministic under VirtualClock (dwell timing reads sample time, not
the wall), and the verifier's per-dispatch accounting lands in metrics."""

from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops.slo import (BREACH, OK, WARN, SloRule,
                                      SloWatchdog, aggregate_status)
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.timeseries import (TimeSeries,
                                              aggregate_summaries,
                                              summarize_samples)


def _app(cfg=None):
    cfg = cfg or get_test_config()
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


# ------------------------------------------------------------- the ring --

def test_ring_bound_and_eviction_accounting():
    ts = TimeSeries(capacity=5)
    for i in range(8):
        ts.append({"t": float(i)})
    assert len(ts) == 5
    assert ts.dropped == 3
    kept = [s["cursor"] for s in ts.samples()]
    assert kept == [4, 5, 6, 7, 8]       # oldest evicted, order kept


def test_since_cursor_incremental_and_gap_resync():
    ts = TimeSeries(capacity=4)
    for i in range(3):
        ts.append({"t": float(i)})
    full, reset = ts.since(None)
    assert reset and len(full) == 3
    token = ts.cursor_token()
    ts.append({"t": 3.0})
    newer, reset = ts.since(token)
    assert not reset and [s["cursor"] for s in newer] == [4]
    # caught-up scraper: empty increment, no reset
    newer, reset = ts.since(ts.cursor_token())
    assert newer == [] and not reset
    # push the continuation point off the ring: full buffer + reset
    for i in range(6):
        ts.append({"t": 10.0 + i})
    behind, reset = ts.since(token)
    assert reset and len(behind) == 4


def test_limit_truncates_from_the_oldest_and_cursor_continues():
    """A limited reply must serve the OLDEST pending samples and
    point its cursor at the last one served — chaining limited
    scrapes walks the whole series with no silent gap."""
    ts = TimeSeries(capacity=16)
    for i in range(7):
        ts.append({"t": float(i)})
    doc = ts.to_doc(since=None, limit=3)
    assert doc["truncated"] is True
    assert [s["cursor"] for s in doc["samples"]] == [1, 2, 3]
    doc2 = ts.to_doc(since=doc["cursor"], limit=3)
    assert doc2["reset"] is False
    assert [s["cursor"] for s in doc2["samples"]] == [4, 5, 6]
    doc3 = ts.to_doc(since=doc2["cursor"], limit=3)
    assert [s["cursor"] for s in doc3["samples"]] == [7]
    assert doc3["truncated"] is False
    # limit=0 serves nothing and does NOT advance the cursor
    doc4 = ts.to_doc(since=doc2["cursor"], limit=0)
    assert doc4["samples"] == []
    assert ts.to_doc(since=doc4["cursor"])["samples"][0]["cursor"] == 7


def test_since_cursor_across_restart_and_clear():
    """A restarted node (new TimeSeries) or a clearmetrics MUST
    invalidate outstanding cursors via the epoch, never serve a
    silent gap."""
    a = TimeSeries(capacity=8)
    a.append({"t": 0.0})
    token = a.cursor_token()
    b = TimeSeries(capacity=8)           # the restarted node's ring
    assert a.epoch != b.epoch
    b.append({"t": 1.0})
    samples, reset = b.since(token)
    assert reset and len(samples) == 1   # full resync, flagged
    # clear: same object, rotated epoch, cursor restarts at 1
    a.clear()
    assert a.since(token)[1] is True
    a.append({"t": 2.0})
    assert a.samples()[0]["cursor"] == 1


# --------------------------------------------------------- the sampler --

def test_sampler_fires_on_virtual_clock_and_stays_bounded():
    cfg = get_test_config()
    cfg.TELEMETRY_SAMPLE_PERIOD = 1.0
    cfg.TELEMETRY_RING_CAPACITY = 10
    app = _app(cfg)
    try:
        app.clock.crank_for(25.0)
        series = app.telemetry.series
        assert len(series) == 10                  # capacity, not 25
        assert series.dropped >= 10
        s = series.latest()
        # the snapshot families the SLO rules and artifacts read
        for key in ("t", "wall", "ledger", "close", "tx_e2e",
                    "slot_p99_ms", "verify", "dispatch", "breaker",
                    "breaker_open", "flood", "host"):
            assert key in s, key
        # virtual-clock sampling: timestamps step the virtual period
        ts = [x["t"] for x in series.samples()]
        assert ts == sorted(ts)
        assert all(abs((b - a) - 1.0) < 1e-6
                   for a, b in zip(ts, ts[1:]))
    finally:
        app.shutdown()


def test_sampler_determinism_under_virtual_clock():
    """Two identically-seeded apps sampled over the same virtual span
    produce identical series modulo wall-clock/host fields — the
    chaos-repro contract extended to telemetry."""
    def run():
        cfg = get_test_config(instance=7777)
        cfg.TELEMETRY_SAMPLE_PERIOD = 0.5
        app = _app(cfg)
        try:
            app.manual_close()
            app.clock.crank_for(5.0)
            out = []
            for s in app.telemetry.series.samples():
                c = {k: v for k, v in s.items()
                     if k not in ("wall", "host", "close", "tx_e2e")}
                out.append(c)
            return out
        finally:
            app.shutdown()

    assert run() == run()


def test_clearmetrics_resets_series_cursors_and_slo_state():
    cfg = get_test_config()
    app = _app(cfg)
    try:
        app.telemetry.sample_now()
        app.slo.observe({"t": 0.0, "close": {"p99_ms": 1e9,
                                             "count": 1}})
        assert app.slo.status()["rules"]["close_p99"]["verdict"] \
            == BREACH
        epoch = app.telemetry.series.epoch
        token = app.telemetry.series.cursor_token()
        app.command_handler.handle("clearmetrics", {})
        assert len(app.telemetry.series) == 0
        assert app.telemetry.series.epoch != epoch
        assert app.telemetry.series.since(token)[1] is True
        st = app.slo.status()
        assert st["overall"] == OK and st["evaluations"] == 0
        assert st["rules"]["close_p99"]["breaches"] == 0
    finally:
        app.shutdown()


def test_timeseries_and_slo_admin_routes():
    cfg = get_test_config()
    app = _app(cfg)
    try:
        app.manual_close()
        app.telemetry.sample_now()
        doc = app.command_handler.handle("timeseries", {})["timeseries"]
        assert doc["reset"] is True and len(doc["samples"]) == 1
        token = doc["cursor"]
        app.telemetry.sample_now()
        inc = app.command_handler.handle(
            "timeseries", {"since": token})["timeseries"]
        assert inc["reset"] is False and len(inc["samples"]) == 1
        # limit caps the reply, summary returns the bounded form
        app.telemetry.sample_now()
        lim = app.command_handler.handle(
            "timeseries", {"limit": "1"})["timeseries"]
        assert len(lim["samples"]) == 1
        summ = app.command_handler.handle(
            "timeseries", {"summary": "1"})["timeseries"]["summary"]
        assert summ["samples"] == 3 and "host_load" in summ
        slo = app.command_handler.handle("slo", {})["slo"]
        assert slo["overall"] in (OK, WARN, BREACH)
        assert set(slo["rules"]) == {"close_p99", "tx_e2e_p99",
                                     "breaker_open_dwell",
                                     "duplicate_ratio", "read_p99"}
    finally:
        app.shutdown()


# ------------------------------------------------------------- the SLO --

def _sample(t, **over):
    s = {"t": t, "close": {"count": 1, "p99_ms": 100.0},
         "tx_e2e": {"count": 0}, "breaker_open": 0.0,
         "flood": {"duplicate_ratio": 1.0}}
    s.update(over)
    return s


def test_slo_threshold_warn_and_breach():
    reg = MetricsRegistry()
    wd = SloWatchdog([SloRule("close_p99", ("close", "p99_ms"),
                              1000.0)], metrics=reg)
    wd.observe(_sample(0.0))
    assert wd.status()["rules"]["close_p99"]["verdict"] == OK
    wd.observe(_sample(1.0, close={"count": 1, "p99_ms": 850.0}))
    assert wd.status()["rules"]["close_p99"]["verdict"] == WARN
    wd.observe(_sample(2.0, close={"count": 1, "p99_ms": 1500.0}))
    st = wd.status()["rules"]["close_p99"]
    assert st["verdict"] == BREACH and st["breaches"] == 1
    # verdict counters rode the registry (Prometheus-exportable)
    assert reg.new_counter("slo.close_p99.breach").count == 1
    assert reg.new_counter("slo.close_p99.warn").count == 1
    assert reg.new_counter("slo.close_p99.ok").count == 1
    # recovery
    wd.observe(_sample(3.0))
    assert wd.overall() == OK


def test_slo_dwell_is_deterministic_in_sample_time():
    """Breaker-OPEN dwell: WARN while the breach window is inside the
    dwell, BREACH exactly once sample-time says the dwell elapsed —
    wall clock never consulted."""
    wd = SloWatchdog([SloRule("breaker", ("breaker_open",), 0.5,
                              warn_ratio=1.0, dwell_s=10.0)])
    wd.observe(_sample(0.0, breaker_open=1.0))
    assert wd.status()["rules"]["breaker"]["verdict"] == WARN
    wd.observe(_sample(9.0, breaker_open=1.0))
    assert wd.status()["rules"]["breaker"]["verdict"] == WARN
    wd.observe(_sample(10.0, breaker_open=1.0))
    assert wd.status()["rules"]["breaker"]["verdict"] == BREACH
    # a close resets the window: the next OPEN starts a fresh dwell
    wd.observe(_sample(11.0))
    wd.observe(_sample(12.0, breaker_open=1.0))
    assert wd.status()["rules"]["breaker"]["verdict"] == WARN


def test_slo_missing_sections_are_ok_not_breach():
    wd = SloWatchdog([SloRule("dup", ("flood", "duplicate_ratio"),
                              2.0)])
    wd.observe({"t": 0.0, "flood": None})
    wd.observe({"t": 1.0})
    assert wd.overall() == OK
    assert wd.status()["rules"]["dup"]["value"] is None


def test_slo_aggregate_status_takes_worst():
    a = {"overall": OK, "rules": {"close_p99": {
        "verdict": OK, "breaches": 0, "warns": 1, "threshold": 1.0}}}
    b = {"overall": BREACH, "rules": {"close_p99": {
        "verdict": BREACH, "breaches": 3, "warns": 0,
        "threshold": 1.0}}}
    agg = aggregate_status([a, b, None])
    assert agg["overall"] == BREACH and agg["nodes"] == 2
    assert agg["rules"]["close_p99"]["breaches"] == 3
    assert agg["rules"]["close_p99"]["warns"] == 1


# ------------------------------------------- dispatch accounting + sums --

def test_verifier_dispatch_accounting():
    """Per-dispatch device telemetry (ROADMAP item 1 groundwork):
    batch size, padding waste to the power-of-two bucket, and a
    dispatch wall-time observation per collect."""
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    reg = MetricsRegistry()
    v = TpuBatchVerifier(device_min_batch=1, metrics=reg)
    assert all(v.verify_tuples(_sig_items(5)))
    batch = reg.new_histogram("crypto.verify.dispatch.batch")
    pad = reg.new_histogram("crypto.verify.dispatch.padding")
    wall = reg.new_timer("crypto.verify.dispatch.wall")
    assert batch.count == 1 and batch._sum == 5.0
    assert pad.count == 1 and pad._sum == 3.0       # bucket 8, n 5
    assert wall.count == 1
    # the small-batch host bypass does NOT count as a device dispatch
    v2 = TpuBatchVerifier(device_min_batch=64, metrics=reg)
    assert all(v2.verify_tuples(_sig_items(2)))
    assert batch.count == 1


def _sig_items(n):
    import hashlib

    from stellar_core_tpu.crypto import ed25519_ref as ref
    seed = bytes(range(32))
    pub = ref.secret_to_public(seed)
    out = []
    for i in range(n):
        msg = hashlib.sha256(b"ts-%d" % i).digest()
        out.append((pub, ref.sign(seed, msg), msg))
    return out


def test_summarize_and_aggregate():
    samples = [
        {"t": 0.0, "host": {"load1": 1.0},
         "close": {"count": 1, "p99_ms": 10.0},
         "tx_e2e": {"count": 0},
         "verify": {"queue_pending": 3, "queue_inflight": 0},
         "flood": {"duplicate_ratio": 1.5}, "breaker_open": 0.0},
        {"t": 4.0, "host": {"load1": 3.0},
         "close": {"count": 2, "p99_ms": 20.0},
         "tx_e2e": {"count": 0},
         "verify": {"queue_pending": 1, "queue_inflight": 2},
         "flood": {"duplicate_ratio": 2.5}, "breaker_open": 1.0},
    ]
    s = summarize_samples(samples)
    assert s["samples"] == 2 and s["span_s"] == 4.0
    assert s["host_load"] == {"min": 1.0, "mean": 2.0, "max": 3.0}
    assert s["close_p99_ms_max"] == 20.0
    assert s["queue_pending_max"] == 3
    assert s["duplicate_ratio_last"] == 2.5
    assert s["breaker_open_samples"] == 1
    agg = aggregate_summaries([s, summarize_samples([])])
    assert agg["samples"] == 2 and agg["nodes"] == 1
    assert summarize_samples([]) == {"samples": 0}
