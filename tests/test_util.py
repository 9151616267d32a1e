"""Util-layer tests (reference behaviors: src/util/test/TimerTests.cpp,
SchedulerTests.cpp, and the verify-cache usage in crypto/SecretKey.cpp)."""

import pytest

from stellar_core_tpu.util import (
    VirtualClock, VirtualTimer, ClockMode, Scheduler, ActionType,
    RandomEvictionCache, releaseAssert, AssertionFailed,
)
from stellar_core_tpu.util.metrics import MetricsRegistry


def test_virtual_clock_starts_at_zero():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    assert clock.now() == 0.0


def test_virtual_timer_fires_in_order():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    fired = []
    for delay, tag in [(3.0, "c"), (1.0, "a"), (2.0, "b")]:
        t = VirtualTimer(clock)
        t.expires_from_now(delay)
        t.async_wait(lambda tag=tag: fired.append(tag))
    # nothing due yet
    assert clock.crank(block=False) == 0
    # blocking cranks advance virtual time to each event
    while clock.crank(block=True):
        pass
    assert fired == ["a", "b", "c"]
    assert clock.now() == 3.0


def test_virtual_timer_cancel():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    fired, cancelled = [], []
    t = VirtualTimer(clock)
    t.expires_from_now(1.0)
    t.async_wait(lambda: fired.append(1), on_cancel=lambda: cancelled.append(1))
    t.cancel()
    clock.crank_for(2.0)
    assert fired == [] and cancelled == [1]


def test_crank_until():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    hits = []
    t = VirtualTimer(clock)
    t.expires_from_now(5.0)
    t.async_wait(lambda: hits.append(1))
    assert clock.crank_until(lambda: bool(hits), timeout=10.0)
    assert not clock.crank_until(lambda: len(hits) > 1, timeout=1.0)


def test_scheduler_fairness():
    s = Scheduler()
    order = []
    for i in range(3):
        s.enqueue("a", lambda i=i: order.append(("a", i)))
        s.enqueue("b", lambda i=i: order.append(("b", i)))
    s.run_all()
    # FIFO within queues; both queues interleave
    assert [x for x in order if x[0] == "a"] == [("a", 0), ("a", 1), ("a", 2)]
    assert [x for x in order if x[0] == "b"] == [("b", 0), ("b", 1), ("b", 2)]
    assert s.stats_actions_run == 6


def test_scheduler_sheds_droppable():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    s = Scheduler(clock, latency_window=5.0)
    ran = []
    s.enqueue("q", lambda: ran.append("d"), ActionType.DROPPABLE)
    s.enqueue("q", lambda: ran.append("n"), ActionType.NORMAL)
    clock.set_virtual_time(10.0)  # everything in q is now stale
    s.run_all()
    assert ran == ["n"]
    assert s.stats_actions_dropped == 1


def test_random_eviction_cache_bounds_and_counters():
    c = RandomEvictionCache(max_size=16, seed=7)
    for i in range(100):
        c.put(i, i * 2)
    assert len(c) == 16
    assert c.inserts == 100
    hits_before = c.hits
    found = sum(1 for i in range(100) if c.maybe_get(i) is not None)
    assert found == 16
    assert c.hits == hits_before + 16
    assert c.misses == 84
    # overwrite does not grow
    for i in range(100):
        c.put(1000, i)
    assert len(c) == 16
    assert c.maybe_get(1000) == 99


def test_release_assert():
    releaseAssert(True)
    with pytest.raises(AssertionFailed):
        releaseAssert(False, "boom")


def test_metrics_registry():
    m = MetricsRegistry()
    m.new_counter("ledger.age.closed").inc(3)
    m.new_meter("scp.envelope.receive").mark(10)
    t = m.new_timer("ledger.transaction.apply")
    with t.time_scope():
        pass
    t.update(0.5)
    j = m.to_json()
    assert j["ledger.age.closed"]["count"] == 3
    assert j["scp.envelope.receive"]["count"] == 10
    assert j["ledger.transaction.apply"]["count"] == 2
    # same name returns same object
    assert m.new_counter("ledger.age.closed").count == 3


def test_gc_policy_install_and_collect():
    """util/gcpolicy (ISSUE 12): install is process-wide idempotent
    (the test process's first Application already installed it), the
    gen2 auto-threshold is pushed out so automatic full-heap scans
    cannot land inside a ledger close, and the explicit maintenance/
    teardown passes still reclaim reference cycles."""
    import gc

    from stellar_core_tpu.util import gcpolicy

    first = gcpolicy.install()
    assert gcpolicy.install() is False    # idempotent from here on
    if not first:
        # an Application was built earlier in the suite: the policy
        # must already be live
        assert gc.get_threshold()[2] >= 1_000_000
    # ISSUE 38: and generation 0's, so that no young pass falls inside
    # a close on a count of allocations
    assert gc.get_threshold()[0] == gcpolicy.YOUNG_THRESHOLD

    class Cyc:
        pass

    a, b = Cyc(), Cyc()
    a.other, b.other = b, a
    del a, b
    # the explicit passes are the sanctioned full collections
    assert gcpolicy.maintenance_collect() >= 0
    assert gcpolicy.teardown_collect() >= 0
