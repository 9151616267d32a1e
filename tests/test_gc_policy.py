"""When the young collector runs (ISSUE 38): `util/gcpolicy.py` puts
generation 0's threshold far above what a close allocates, so no pass
falls inside `ledger.closeLedger` on a count of allocations; cycles are
reclaimed by the two passes somebody asks for; and the collector's
yield, `runtime.gc.collected`, is published while a recorder records."""

import gc
import time
import weakref

import pytest

from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util import gcpolicy, tracing
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_standalone_app as m1
from txtest_utils import op_payment

PAYMENTS = 300
PASSES = {"maintenance": gcpolicy.maintenance_collect,
          "teardown": lambda: gcpolicy.teardown_collect(force=True)}


@pytest.fixture(autouse=True)
def _policy_as_installed():
    """Every test starts and ends under the installed policy, with no
    recorder left recording."""
    gcpolicy.install()
    before = gc.get_threshold()
    yield
    gc.set_threshold(*before)
    with tracing._state_lock:
        del tracing._active[:]
        tracing.ENABLED = False
        if tracing._on_gc in gc.callbacks:
            gc.callbacks.remove(tracing._on_gc)


def _node():
    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = PAYMENTS
    cfg.LIMIT_TX_QUEUE_SOURCE_ACCOUNT = False   # one payer, in sequence
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()          # the upgrade of the set size
    return app


@pytest.fixture
def app():
    a = _node()
    yield a
    a.shutdown()


def _close_of_payments(app):
    """One close of `PAYMENTS` payments with the recorder on: the young
    passes (generation 0 or 1) that ended inside its
    `ledger.closeLedger` span, and the hash it closed to."""
    master = m1.master_account(app)
    for i in range(PAYMENTS):
        status = m1.submit(app, master.tx([op_payment(master.muxed, 1 + i)]))
        assert status["status"] == "PENDING", status
    ends = []

    def on_gc(phase, info):
        if phase == "stop" and info["generation"] < 2:
            ends.append(time.perf_counter())
    rec = app.flight_recorder
    rec.start()
    gc.callbacks.append(on_gc)
    try:
        app.manual_close()
    finally:
        gc.callbacks.remove(on_gc)
        rec.stop()
    spans = [e["ts"] for e in rec.to_chrome_trace()["traceEvents"]
             if e["name"] == "ledger.closeLedger" and e["ph"] in "BE"]
    assert len(spans) == 2
    lo, hi = (rec.t0 + ts / 1e6 for ts in spans)
    assert app.metrics.to_json()["ledger.transaction.count"]["count"] \
        >= PAYMENTS
    closed_to = bytes(app.ledger_manager.get_last_closed_ledger_hash())
    return sum(1 for t in ends if lo <= t <= hi), closed_to


def test_install_sets_the_young_threshold_once():
    assert gcpolicy.install() is False
    young, _, full = gc.get_threshold()
    assert young == gcpolicy.YOUNG_THRESHOLD >= 1_000_000
    assert full >= 1_000_000
    # a second install is no reset: what a caller laid on stays
    gc.set_threshold(700, 10, full)
    assert gcpolicy.install() is False
    assert gc.get_threshold()[0] == 700


def test_no_young_pass_inside_a_close_on_a_count_of_allocations():
    """The tree before ISSUE 38 ran ~50 passes inside this close; under
    the policy a pass needs more net allocations than a checkpoint
    keeps, and the close reaches the hash it reaches under CPython's
    own threshold: when the collector runs decides nothing."""
    results = {}
    for young in (gcpolicy.YOUNG_THRESHOLD, 700):
        _, t1, t2 = gc.get_threshold()
        gc.set_threshold(young, t1, t2)
        app = _node()
        try:
            results[young] = _close_of_payments(app)
        finally:
            app.shutdown()
    passes, closed_to = results[gcpolicy.YOUNG_THRESHOLD]
    passes_700, closed_to_700 = results[700]
    assert passes <= 2
    assert passes_700 > 10          # the control: the count sees them
    assert closed_to == closed_to_700


@pytest.mark.parametrize("which", sorted(PASSES))
def test_a_cycle_made_inside_a_close_dies_in_the_pass_asked_for(
        app, which, monkeypatch):
    class Node:
        pass
    made = []
    lm = app.ledger_manager
    inner = lm._close_ledger

    def closing(*args, **kw):
        a, b = Node(), Node()
        a.other, b.other = b, a
        made.append(weakref.ref(a))
        return inner(*args, **kw)
    monkeypatch.setattr(lm, "_close_ledger", closing)
    app.manual_close()
    assert len(made) == 1
    # nothing came for it unasked: a close is far under the threshold
    assert made[0]() is not None
    assert PASSES[which]() >= 2
    assert made[0]() is None


@pytest.mark.parametrize("which", sorted(PASSES))
def test_a_pass_asked_for_leaves_the_threshold(which):
    before = gc.get_threshold()
    assert before[0] == gcpolicy.YOUNG_THRESHOLD
    PASSES[which]()
    assert gc.get_threshold() == before


def test_collected_is_published_while_a_recorder_records(app):
    class Node:
        pass

    def cycles(n):
        for _ in range(n):
            a, b = Node(), Node()
            a.other, b.other = b, a
    name = "runtime.gc.collected"
    cycles(3)
    gc.collect(0)
    assert name not in app.metrics.to_json()    # nothing recorded it
    app.flight_recorder.start()
    assert app.metrics.to_json()[name]["count"] == 0
    cycles(5)
    gc.collect(0)
    # two objects a cycle and each one's `__dict__`
    collected = app.metrics.to_json()[name]["count"]
    assert collected >= 10
    cycles(5)
    gc.collect()                    # a full pass's yield counts too
    assert app.metrics.to_json()[name]["count"] >= collected + 10
    app.flight_recorder.stop()
    cycles(5)
    gc.collect(0)
    after = app.metrics.to_json()[name]["count"]
    cycles(5)
    gc.collect(0)
    assert app.metrics.to_json()[name]["count"] == after


class _Clock:
    """`time` as `util/tracing.py` sees it, with a `perf_counter` that
    moves when the test moves it."""

    time = staticmethod(time.time)

    def __init__(self):
        self.now = time.perf_counter()

    def perf_counter(self) -> float:
        return self.now


def test_a_long_pass_of_generation_0_is_an_instant(app, monkeypatch):
    """The long passes are generation 0's under the policy: one of a
    millisecond or more is written whatever its generation, a shorter
    one of generation 0 is not."""
    # the two passes differ in the stamps they are given, not in how
    # long the scheduler keeps this thread between two calls
    clock = _Clock()
    monkeypatch.setattr(tracing, "time", clock)
    rec = app.flight_recorder
    rec.start()
    for seconds in (2e-3, 0.0):
        tracing._on_gc("start", {"generation": 0})
        clock.now += seconds
        tracing._on_gc("stop", {"generation": 0, "collected": 7,
                                "uncollectable": 0})
    rec.stop()
    instants = [e["args"] for e in rec.to_chrome_trace()["traceEvents"]
                if e["ph"] == "i" and e["name"] == "runtime.gc"
                and e["args"]["collected"] == 7]
    assert len(instants) == 1 and instants[0]["generation"] == 0
    assert 2.0 <= instants[0]["ms"] < 1000.0
    assert app.perf.report()["runtime.gc"]["count"] >= 2
