"""What a thread ran and what it stood still (ISSUE 37): on-CPU seconds
beside wall seconds in every zone, the derived `<zone>.onCpu` entry of
`ZoneRegistry.report()`, the kernel's account of a thread once a close and
once a completion job, the collector's seconds while a recorder records,
a stall that names its cause — and that none of it sits on a per-item
path, reads `/proc` or installs a `gc.callbacks` entry while no recorder
is active."""

import gc
import logging
import sys
import threading
import time

import pytest

from stellar_core_tpu.crypto import keys
from stellar_core_tpu.crypto.keys import SecretKey, clear_verify_cache
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
from stellar_core_tpu.tx import signature_checker
from stellar_core_tpu.util import perf, tracing
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.perf import ON_CPU, ZoneRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_standalone_app as m1
from txtest_utils import op_payment

CLOSING = ("runtime.closing.onCpu", "runtime.closing.runDelay")
COMPLETION = ("runtime.completion.onCpu", "runtime.completion.runDelay")


@pytest.fixture(autouse=True)
def _no_leftover_tracing():
    yield
    with tracing._state_lock:
        del tracing._active[:]
        tracing.ENABLED = False
        if tracing._on_gc in gc.callbacks:
            gc.callbacks.remove(tracing._on_gc)


@pytest.fixture
def app():
    a = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                           get_test_config())
    a.start()
    yield a
    a.shutdown()


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _zone_ms(reg: ZoneRegistry, name: str):
    """(wall ms, on-CPU ms) of one zone of a report."""
    z = reg.report()[name]
    return z["total_ms"], z["cpu_ms"]


# ------------------------------------------------ (a) on-CPU in a zone --

def test_a_zone_round_a_sleep_reads_wall_and_hardly_any_cpu():
    reg = ZoneRegistry()
    with reg.zone("z"):
        time.sleep(0.05)
    wall, cpu = _zone_ms(reg, "z")
    assert wall >= 50.0 and 0.0 <= cpu < 10.0


def test_a_zone_round_a_busy_loop_reads_cpu_within_a_tenth_of_wall():
    # a tenant of the sandbox's cores can keep this thread off the CPU
    # for a slice: the best of a few tries is the clock's own doing
    best = 0.0
    for attempt in range(8):
        reg = ZoneRegistry()
        with reg.zone("z"):
            _spin(0.05)
        wall, cpu = _zone_ms(reg, "z")
        assert cpu <= 1.02 * wall
        best = max(best, cpu / wall)
        if best >= 0.9:
            break
    assert best >= 0.9


def test_a_zone_sees_the_wait_for_the_interpreter():
    """With a second thread spinning in pure Python the zone's thread
    has the interpreter for part of its wall time only: wall − on-CPU
    is that wait."""
    stop = threading.Event()

    def spinner():
        while not stop.is_set():
            pass
    t = threading.Thread(target=spinner, daemon=True)
    reg = ZoneRegistry()
    old = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    t.start()
    try:
        with reg.zone("z"):
            _spin(0.3)
    finally:
        stop.set()
        sys.setswitchinterval(old)
        t.join(10)
    assert not t.is_alive()
    wall, cpu = _zone_ms(reg, "z")
    assert wall - cpu > 0.1 * wall, (wall, cpu)


def test_add_with_cpu_seconds_and_zone_agree():
    a, b = ZoneRegistry(), ZoneRegistry()
    for _ in range(3):
        with a.zone("z"):
            _spin(0.002)
    ra = a.report()
    b.add("z", ra["z"]["total_ms"] / 1e3, count=3,
          cpu_seconds=ra["z"]["cpu_ms"] / 1e3)
    rb = b.report()
    assert sorted(ra) == sorted(rb) == ["z", "z" + ON_CPU]
    for name in ra:
        assert rb[name]["count"] == ra[name]["count"] == 3
        assert rb[name]["total_ms"] == pytest.approx(ra[name]["total_ms"],
                                                     abs=1e-3)
        assert 0 < rb[name]["max_ms"] <= ra[name]["max_ms"]
    assert rb["z"]["cpu_ms"] == rb["z" + ON_CPU]["total_ms"]


@pytest.mark.parametrize("cpu_seconds,derived", [(None, False),
                                                 (0.0, True),
                                                 (0.25, True)])
def test_report_lists_the_derived_entry_only_where_cpu_was_measured(
        cpu_seconds, derived):
    reg = ZoneRegistry()
    reg.add("site", 0.5, 2, cpu_seconds)
    report = reg.report()
    assert ("site" + ON_CPU in report) == derived
    assert ("cpu_ms" in report["site"]) == derived
    if derived:
        assert report["site" + ON_CPU] == {
            "count": 2, "total_ms": cpu_seconds * 1e3,
            "mean_ms": cpu_seconds * 1e3 / 2,
            "max_ms": cpu_seconds * 1e3 / 2}
    else:
        assert report == {"site": {"count": 2, "total_ms": 500.0,
                                   "mean_ms": 250.0, "max_ms": 250.0}}


def test_a_partly_measured_zone_derives_the_count_that_was_measured():
    """A site that read the thread clock for some reports only (the
    recorder came on in between): the derived entry counts those hits,
    and a reader that wants wall and on-CPU of the same hits sees the
    counts differ."""
    reg = ZoneRegistry()
    reg.add("site", 0.5, 2)
    reg.add("site", 0.5, 3, 0.1)
    report = reg.report()
    assert report["site"]["count"] == 5
    assert report["site" + ON_CPU]["count"] == 3
    assert report["site"]["cpu_ms"] == 100.0


def test_zone_into_is_one_clock_pair_and_fills_the_sink(monkeypatch):
    reads = []
    real = time.perf_counter
    monkeypatch.setattr(time, "perf_counter",
                        lambda: reads.append(1) or real())
    reg, sink = ZoneRegistry(), {"phase": 1.0}
    with reg.zone_into("phase", sink):
        pass
    monkeypatch.undo()
    assert len(reads) == 2
    assert sink["phase"] == pytest.approx(
        1.0 + reg.report()["phase"]["total_ms"] / 1e3, abs=1e-6)


def test_reset_empties_zones_and_derived_entries():
    reg = ZoneRegistry()
    with reg.zone("z"):
        pass
    assert len(reg.report()) == 2
    reg.reset()
    assert reg.report() == {}


def test_clearmetrics_empties_both(app):
    app.manual_close()
    h = app.command_handler
    before = h.handle("perf", {})["perf"]
    assert "ledger.closeLedger" in before \
        and "ledger.closeLedger" + ON_CPU in before
    assert before["ledger.closeLedger"]["cpu_ms"] \
        <= 1.02 * before["ledger.closeLedger"]["total_ms"] + 1.0
    assert h.handle("clearmetrics")["status"] == "ok"
    assert h.handle("perf", {})["perf"] == {}
    zones = h.handle("metrics", {})["perf_zones"]
    assert not [z for z in zones if z.endswith(ON_CPU)]


def test_the_end_of_a_zones_span_carries_its_cpu_us():
    rec, reg = tracing.FlightRecorder(), ZoneRegistry()
    reg.tracer = rec
    rec.start()
    try:
        with reg.zone("outer", targs={"seq": 7}):
            _spin(0.002)
    finally:
        rec.stop()
    ends = [e for e in rec.to_chrome_trace()["traceEvents"]
            if e["ph"] == "E"]
    assert [e["name"] for e in ends] == ["outer"]
    assert 1000.0 <= ends[0]["args"]["cpu_us"] \
        <= 1.02 * reg.report()["outer"]["total_ms"] * 1e3


# ------------------------- (a) the per-item sites, recorder on and off --

@pytest.fixture
def no_thread_clock(monkeypatch):
    def trip():
        raise AssertionError("the thread clock was read on a per-item "
                             "path")
    monkeypatch.setattr(time, "thread_time", trip)
    yield
    monkeypatch.undo()


def _payments(app, n):
    """`n` payments of the master account, signed, in sequence order."""
    master = m1.master_account(app)
    return [master.tx([op_payment(master.muxed, 1 + i)]) for i in range(n)]


def test_per_item_sites_read_no_thread_clock_with_no_recorder(
        app, no_thread_clock):
    assert tracing.ENABLED is False
    [frame] = _payments(app, 1)
    pub, sig, msg = signature_checker.collect_signature_tuples([frame])[0]
    clear_verify_cache()
    assert keys.verify_sig_uncached(pub, sig, msg)
    from stellar_core_tpu.herder.tx_queue import AddResult
    assert app.herder.recv_transaction(frame) == \
        AddResult.ADD_STATUS_PENDING
    assert app.herder._recv_count == 1 and app.herder._recv_run is None
    assert keys._native_count >= 1


def test_a_run_of_admissions_reads_the_thread_clock_twice_a_close(
        app, monkeypatch):
    """While a recorder records: one read at the first call after a
    close and one at the start of the next close, however many calls
    lie between; `verify_sig_uncached` reads none at all. (Up to four
    closes: a tenant of the sandbox's cores can hold the thread between
    two calls for longer than a fiftieth of so short a run.)"""
    rounds, each = 4, 25
    frames = _payments(app, rounds * each)
    app.command_handler.handle("clearmetrics")
    app.flight_recorder.start()
    clear_verify_cache()
    site = "herder.recvTransaction"
    real = time.thread_time
    for r in range(rounds):
        reads = []
        monkeypatch.setattr(time, "thread_time",
                            lambda: reads.append(1) or real())
        for frame in frames[r * each:(r + 1) * each]:
            app.herder.recv_transaction(frame)
        assert len(reads) == 1 and app.herder._recv_run is not None
        app.herder._end_recv_run()
        monkeypatch.undo()
        assert len(reads) == 2 and app.herder._recv_run is None
        app.manual_close()
        report = app.perf.report()
        if site + ON_CPU in report:
            break
    assert report[site]["count"] == (r + 1) * each
    assert report[site + ON_CPU]["count"] == each
    assert 0.0 <= report[site]["cpu_ms"] \
        <= 1.02 * report[site]["total_ms"] + 0.05
    assert "crypto.verify.native" + ON_CPU not in report
    assert report["crypto.verify.native"]["count"] >= each
    # neither site puts a span into the recording
    names = {e["name"] for e in
             app.flight_recorder.to_chrome_trace()["traceEvents"]}
    assert not {site, "crypto.verify.native"} & names


def test_admissions_that_are_not_back_to_back_report_no_on_cpu_seconds(
        app):
    """The run's on-CPU seconds stand for the calls only where nothing
    else of weight ran between them."""
    frames = _payments(app, 2)
    app.command_handler.handle("clearmetrics")
    app.flight_recorder.start()
    app.herder.recv_transaction(frames[0])
    _spin(0.02)                 # the thread does something else
    app.herder.recv_transaction(frames[1])
    app.manual_close()
    report = app.perf.report()
    assert report["herder.recvTransaction"]["count"] == 2
    assert "herder.recvTransaction" + ON_CPU not in report


def test_a_recorder_that_comes_on_inside_a_close_measures_the_next(app):
    frames = _payments(app, 2)
    app.command_handler.handle("clearmetrics")
    app.herder.recv_transaction(frames[0])
    app.flight_recorder.start()
    app.herder.recv_transaction(frames[1])
    assert app.herder._recv_run is None     # not the first call
    app.manual_close()
    assert "herder.recvTransaction" + ON_CPU not in app.perf.report()
    [frame] = _payments(app, 1)
    app.herder.recv_transaction(frame)
    assert app.herder._recv_run is not None


# -------------------------------- (b) the thread's account, once a close --

def test_thread_sched_grows_with_the_work_of_this_thread():
    s0 = perf.thread_sched()
    if s0 is None:
        pytest.skip("this host keeps no /proc/thread-self/schedstat")
    # 50 ms of this thread's own running, however long the host takes
    # to give them (beside five other workers a spin by the wall clock
    # may run for less than 30 of its 50 ms)
    end = time.thread_time() + 0.05
    while time.thread_time() < end:
        pass
    s1 = perf.thread_sched()
    assert s1[0] - s0[0] >= 0.03 and s1[1] >= s0[1] >= 0.0


def test_sched_lap_makes_one_sample_of_each_timer(tmp_path, monkeypatch):
    fake = tmp_path / "schedstat"
    monkeypatch.setattr(perf, "_schedstat", str(fake))
    metrics = MetricsRegistry()
    fake.write_text("1000000000 250000000 7\n")
    s0 = perf.thread_sched()
    assert s0 == (1.0, 0.25)
    fake.write_text("1500000000 260000000 9\n")
    s1 = perf.sched_lap(s0, metrics, "t.onCpu", "t.runDelay")
    assert s1 == (1.5, 0.26)
    doc = metrics.to_json()
    assert (doc["t.onCpu"]["count"], doc["t.runDelay"]["count"]) == (1, 1)
    assert doc["t.onCpu"]["sum"] == pytest.approx(0.5)
    assert doc["t.runDelay"]["sum"] == pytest.approx(0.01)
    # no first reading, no timer and no read
    fake.unlink()
    assert perf.sched_lap(None, metrics, "u.onCpu", "u.runDelay") is None
    assert "u.onCpu" not in metrics.to_json()
    assert perf._schedstat == str(fake)


def test_a_missing_schedstat_is_read_once_and_a_close_publishes_nothing(
        app, tmp_path, monkeypatch):
    opened = []
    real_open = open

    def counting_open(path, *a, **kw):
        opened.append(path)
        return real_open(path, *a, **kw)
    monkeypatch.setattr(perf, "open", counting_open, raising=False)
    monkeypatch.setattr(perf, "_schedstat", str(tmp_path / "not-there"))
    assert perf.thread_sched() is None
    assert perf._schedstat is None and len(opened) == 1
    m1.submit(app, _payments(app, 1)[0])
    app.manual_close()
    app.manual_close()
    app.ledger_manager.join_completion()
    assert perf.thread_sched() is None and len(opened) == 1
    published = app.metrics.to_json()
    assert not [n for n in published if n.startswith("runtime.")]


def test_a_close_and_its_tail_each_give_one_sample(app, tmp_path,
                                                   monkeypatch):
    """... and those are all the `/proc` reads there are: two a close
    on the closing thread, two a job on the completion worker, none a
    transaction or a signature."""
    fake = tmp_path / "schedstat"
    fake.write_text("2000000 1000000 3\n")
    monkeypatch.setattr(perf, "_schedstat", str(fake))
    reads = []
    real_open = open

    def counting_open(path, *a, **kw):
        reads.append(threading.current_thread().name)
        return real_open(path, *a, **kw)
    monkeypatch.setattr(perf, "open", counting_open, raising=False)
    master = m1.master_account(app)
    for i in range(5):
        m1.submit(app, master.tx([op_payment(master.muxed, 1 + i)]))
    app.manual_close()
    app.manual_close()
    app.ledger_manager.join_completion()
    doc = app.metrics.to_json()
    for name in CLOSING + COMPLETION:
        assert doc[name]["type"] == "timer" and doc[name]["count"] == 2
    me = threading.current_thread().name
    assert reads.count(me) == 4
    assert reads.count("close-completion") == 4 and len(reads) == 8


# ------------------------------ (c) the collector, while a recorder records --

def _gc_entries():
    return gc.callbacks.count(tracing._on_gc)


def test_gc_callback_is_there_only_between_start_and_stop():
    assert _gc_entries() == 0
    a, b = tracing.FlightRecorder(), tracing.FlightRecorder()
    a.start()
    assert _gc_entries() == 1
    b.start()
    a.start()                       # a restart installs no second one
    assert _gc_entries() == 1
    a.stop()
    assert _gc_entries() == 1       # b still records
    b.stop()
    b.stop()
    assert _gc_entries() == 0 and tracing.ENABLED is False


def test_gc_callback_goes_with_a_shutdown_that_raises():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    assert _gc_entries() == 0
    app.flight_recorder.start()
    assert _gc_entries() == 1
    real = app.herder.shutdown

    def boom():
        raise RuntimeError("boom")
    app.herder.shutdown = boom
    with pytest.raises(RuntimeError):
        app.shutdown()
    assert _gc_entries() == 0 and tracing.ENABLED is False
    app.herder.shutdown = real
    app.shutdown()


def test_a_collection_is_a_zone_a_counter_and_an_instant(app):
    gc.collect()
    assert "runtime.gc" not in app.perf.report()    # nothing recorded it
    app.flight_recorder.start()
    seconds0 = tracing.gc_seconds
    gc.collect(0)
    gc.collect(1)
    gc.collect(2)
    report = app.perf.report()
    # the zone is generations 0 and 1, which come unasked; a full
    # collection is counted and is an instant, and a scope that
    # overran is told of all three
    assert report["runtime.gc"]["count"] >= 2
    assert "runtime.gc" + ON_CPU not in report      # wall alone
    assert app.metrics.to_json()["runtime.gc.gen2"]["count"] >= 1
    full_s = sum(e["args"]["ms"] for e in
                 app.flight_recorder.to_chrome_trace()["traceEvents"]
                 if e["name"] == "runtime.gc"
                 and e["args"]["generation"] == 2) / 1e3
    assert full_s > 0.0
    assert tracing.gc_seconds - seconds0 == pytest.approx(
        report["runtime.gc"]["total_ms"] / 1e3 + full_s, abs=1e-4)
    app.flight_recorder.stop()
    gc.collect()
    assert app.perf.report()["runtime.gc"]["count"] == \
        report["runtime.gc"]["count"]
    instants = [e for e in
                app.flight_recorder.to_chrome_trace()["traceEvents"]
                if e["ph"] == "i" and e["name"] == "runtime.gc"]
    # generations 1 and 2 always; one of generation 0 only if it took a
    # millisecond or more (ISSUE 38: the long passes are its own now)
    assert {1, 2} <= {e["args"]["generation"] for e in instants}
    assert all(e["args"]["ms"] >= 1.0 for e in instants
               if e["args"]["generation"] == 0)
    assert all(set(e["args"]) == {"generation", "collected", "ms"}
               for e in instants)


def test_a_collection_under_the_registrys_lock_does_not_deadlock(app):
    """A collection begins inside whatever allocates, also under the
    zone registry's lock: the callback takes no lock."""
    app.flight_recorder.start()
    done = threading.Event()

    def work():
        with app.perf._lock:
            gc.collect(1)
        with app.flight_recorder._lock:
            gc.collect(1)
        done.set()
    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(20), "the collector's callback blocked on a lock"
    t.join(10)
    assert app.perf.report()["runtime.gc"]["count"] >= 2


def test_every_recording_node_is_told_of_a_collection():
    apps = [Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                               get_test_config()) for _ in range(3)]
    try:
        for a in apps[:2]:
            a.flight_recorder.start()
        gc.collect(1)
        counts = [a.perf.report().get("runtime.gc", {"count": 0})["count"]
                  for a in apps]
        assert counts[0] >= 1 and counts[1] >= 1 and counts[2] == 0
    finally:
        for a in apps:
            a.shutdown()


# ------------------------------------------ (d) a stall names its cause --

STALL_KEYS = {"zone", "seq", "wall_ms", "on_cpu_ms", "run_delay_ms",
              "voluntary_switches", "involuntary_switches", "gc_ms"}


def test_an_overrun_logs_the_numbers_and_counts_a_stall(caplog):
    reg, rec = ZoneRegistry(), tracing.FlightRecorder()
    reg.tracer, reg.metrics, rec.registry = rec, MetricsRegistry(), reg
    rec.start()
    sched0 = perf.thread_sched()
    try:
        with caplog.at_level(logging.WARNING, logger="stellar.Perf"):
            with reg.log_slow_execution("closeLedger 9", 0.02,
                                        detail=lambda: "phase=1ms",
                                        seq=9, sched0=sched0):
                time.sleep(0.03)
                gc.collect()
    finally:
        rec.stop()
    [line] = [r.getMessage() for r in caplog.records
              if "performance issue" in r.getMessage()]
    assert line.startswith("performance issue: closeLedger 9 took ")
    for part in ("on-CPU ", "run-delay ", " voluntary and ",
                 " involuntary context switches", "gc ", "[phase=1ms]"):
        assert part in line, (part, line)
    assert reg.metrics.to_json()["runtime.stall"]["count"] == 1
    [stall] = [e["args"] for e in rec.to_chrome_trace()["traceEvents"]
               if e["ph"] == "i" and e["name"] == "runtime.stall"]
    assert set(stall) == STALL_KEYS
    assert stall["zone"] == "closeLedger 9" and stall["seq"] == 9
    assert stall["wall_ms"] >= 30.0 > stall["on_cpu_ms"] >= 0.0
    assert stall["voluntary_switches"] >= 1     # the sleep
    assert stall["gc_ms"] > 0.0
    assert (stall["run_delay_ms"] is None) == (sched0 is None)


def test_the_normal_path_logs_counts_and_reads_nothing(caplog,
                                                       monkeypatch):
    reg = ZoneRegistry()
    reg.metrics = MetricsRegistry()

    def trip(*a, **kw):
        raise AssertionError("/proc was read on the normal path")
    monkeypatch.setattr(perf, "open", trip, raising=False)
    with caplog.at_level(logging.WARNING, logger="stellar.Perf"):
        with reg.log_slow_execution("quick", 5.0, sched0=(0.0, 0.0)):
            pass
    assert not caplog.records
    assert reg.metrics.to_json() == {} and reg.report() == {}


def test_an_overrun_with_no_recorder_says_what_it_cannot_know(caplog):
    reg = ZoneRegistry()                     # no metrics, no tracer
    with caplog.at_level(logging.WARNING, logger="stellar.Perf"):
        with reg.log_slow_execution("tail", 0.0):
            pass
    [line] = [r.getMessage() for r in caplog.records]
    assert "run-delay None ms" in line and "gc None ms" in line


def test_a_slow_close_is_a_stall_with_its_seq_and_run_delay(app, caplog,
                                                           monkeypatch):
    real = app.ledger_manager._close_ledger

    def slow(lcd, verify, phases):
        time.sleep(2.05)
        return real(lcd, verify, phases)
    monkeypatch.setattr(app.ledger_manager, "_close_ledger", slow)
    app.flight_recorder.start()
    seq = app.ledger_manager.get_last_closed_ledger_num() + 1
    with caplog.at_level(logging.WARNING, logger="stellar.Perf"):
        app.manual_close()
    assert app.metrics.to_json()["runtime.stall"]["count"] == 1
    [stall] = [e["args"] for e in
               app.flight_recorder.to_chrome_trace()["traceEvents"]
               if e["ph"] == "i" and e["name"] == "runtime.stall"]
    assert stall["zone"] == f"closeLedger {seq}" and stall["seq"] == seq
    assert stall["wall_ms"] >= 2050.0 > stall["on_cpu_ms"]
    assert (stall["run_delay_ms"] is None) == (perf.thread_sched() is None)
    assert any(f"closeLedger {seq} took" in r.getMessage()
               and "applyTx=" in r.getMessage()
               for r in caplog.records)


def test_a_supervised_collect_that_takes_a_second_is_a_stall():
    class SlowCollect:
        def verify_tuples_async(self, items):
            def collect():
                time.sleep(1.05)
                return [True] * len(items)
            return collect
    metrics, reg = MetricsRegistry(), ZoneRegistry()
    reg.metrics = metrics
    sup = BackendSupervisor(SlowCollect(), metrics=metrics, perf=reg,
                            dispatch_deadline_ms=5000.0)
    try:
        sk = SecretKey.pseudo_random_for_testing(3700)
        msg = b"m" * 32
        items = [(sk.public_key().raw, sk.sign(msg), msg)]
        assert sup.verify_tuples(items) == [True]
        assert metrics.to_json()["runtime.stall"]["count"] == 1
    finally:
        sup.shutdown()
