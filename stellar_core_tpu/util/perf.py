"""Lightweight performance zones + slow-execution warnings.

Reference: §5.1 of the survey — the reference vendors the Tracy frame
profiler (602 ``ZoneScoped`` annotations, crypto/SecretKey.cpp:431 etc.)
and a ``LogSlowExecution`` scope timer (util/LogSlowExecution.h, used in
closeLedger :711).  Tracy needs a native GUI protocol; the TPU-native
equivalent is an in-process zone registry: cheap monotonic timers
aggregated per zone (count/total/max), dumped via the admin API or
logged.  JAX device work is profiled separately with jax.profiler; these
zones cover the host-side runtime.

A zone reads two clocks: wall (``perf_counter``) and the calling
thread's on-CPU seconds (``thread_time``, CLOCK_THREAD_CPUTIME_ID), so
that work and standing still can be told apart: wall − on-CPU is what
the thread waited for the interpreter, a file or the device. C code that
has let go of the interpreter (``sqlite3_step``, gzip, the native
verify) counts as on-CPU. What the host did to a runnable thread
(run-delay) is the kernel's number: `thread_sched`.

Each ``Application`` owns a ``ZoneRegistry`` so multi-node in-process
simulations don't cross-contaminate; the module-level helpers use a
process default registry for contexts with no app (CLI tools, library
calls).
"""

from __future__ import annotations

import resource
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from . import tracing
from .logging import get_logger

log = get_logger("Perf")


# `report()` lists every zone that has on-CPU seconds a second time
# under `<zone>.onCpu` (count = the hits whose on-CPU time was measured,
# total_ms = their on-CPU milliseconds), for readers that keep only
# `count` and `total_ms` of an entry. Not a zone of its own: nothing
# opens it, and no recording holds a span of that name.
ON_CPU = ".onCpu"

# the calling thread's account with the kernel's scheduler; set to None
# by the first read that fails, and never opened again
_schedstat: Optional[str] = "/proc/thread-self/schedstat"


def thread_sched() -> Optional[Tuple[float, float]]:
    """(on-CPU seconds, run-delay seconds) of the calling thread since
    it started, as the kernel keeps them: run-delay is the time the
    thread was runnable and not run, which is the host's doing and not
    the program's. One file read of ~10 us: for a site that runs once
    a close, a job or a chunk, never once a transaction. None where
    the kernel keeps no such file."""
    global _schedstat
    if _schedstat is None:
        return None
    try:
        with open(_schedstat, "rb") as f:
            on_cpu, delay = f.read().split()[:2]
        return int(on_cpu) * 1e-9, int(delay) * 1e-9
    except (OSError, ValueError):
        _schedstat = None
        return None


def sched_lap(s0: Optional[Tuple[float, float]], metrics,
              on_cpu: str, run_delay: str
              ) -> Optional[Tuple[float, float]]:
    """One sample for each of the timers `on_cpu` and `run_delay` of
    `metrics`: what the calling thread ran, and what it was kept
    waiting, since its reading `s0`. Returns the new reading. Where
    either reading is None no timer is made."""
    s1 = thread_sched() if s0 is not None else None
    if s1 is not None and metrics is not None:
        metrics.new_timer(on_cpu).update(s1[0] - s0[0])
        metrics.new_timer(run_delay).update(s1[1] - s0[1])
    return s1


class _ZoneStats:
    __slots__ = ("count", "total", "max", "cpu_count", "cpu", "cpu_max")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.cpu_count = 0      # the hits whose on-CPU time was measured
        self.cpu = 0.0
        self.cpu_max = 0.0


def _entry(count: int, total: float, worst: float) -> dict:
    return {"count": count,
            "total_ms": round(total * 1000, 3),
            "mean_ms": round(total / count * 1000, 3) if count else 0.0,
            "max_ms": round(worst * 1000, 3)}


class _Zone:
    """One entry and exit of a zone: what `ZoneRegistry.zone` returns.
    The on-CPU interval lies inside the wall interval, so on-CPU never
    reads above wall by more than the clocks' resolution."""

    __slots__ = ("_reg", "_name", "_targs", "_sink", "_tr", "_t0", "_c0")

    def __init__(self, reg, name, targs, sink):
        self._reg = reg
        self._name = name
        self._targs = targs
        self._sink = sink

    def __enter__(self):
        tr = None
        if tracing.ENABLED:
            tr = self._reg.tracer
            if tr is not None and tr.active:
                tr.begin(self._name, self._targs)
            else:
                tr = None
        self._tr = tr
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._c0
        dt = time.perf_counter() - self._t0
        name = self._name
        if self._tr is not None:
            self._tr.end(name, {"cpu_us": round(cpu * 1e6, 1)})
        reg = self._reg     # a local: `self._reg.add` reads as a write
        reg.add(name, dt, 1, cpu)    # of `_reg` to scripts/analyze.py
        sink = self._sink
        if sink is not None:
            sink[name] = sink.get(name, 0.0) + dt
        return False


class _SlowScope:
    """What `ZoneRegistry.log_slow_execution` returns. Entry reads the
    two clocks and `getrusage`, a normal exit the two clocks; everything
    else happens on an overrun."""

    __slots__ = ("_reg", "_name", "_threshold", "_detail", "_seq",
                 "_sched0", "_t0", "_c0", "_ru0", "_gc0")

    def __init__(self, reg, name, threshold, detail, seq, sched0):
        self._reg = reg
        self._name = name
        self._threshold = threshold
        self._detail = detail
        self._seq = seq
        self._sched0 = sched0

    def __enter__(self):
        self._gc0 = tracing.gc_seconds if tracing.ENABLED else None
        self._ru0 = resource.getrusage(resource.RUSAGE_THREAD)
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._c0
        dt = time.perf_counter() - self._t0
        if dt > self._threshold:
            self._overran(dt, cpu)
        return False

    def _overran(self, dt: float, cpu: float) -> None:
        reg = self._reg
        ru0, ru1 = self._ru0, resource.getrusage(resource.RUSAGE_THREAD)
        sched0 = self._sched0
        sched1 = thread_sched() if sched0 is not None else None
        stall = {"zone": self._name, "seq": self._seq,
                 "wall_ms": round(dt * 1e3, 1),
                 "on_cpu_ms": round(cpu * 1e3, 1),
                 # None: the scope was given no reading of the thread's
                 # account (only a close has one), or the host keeps none
                 "run_delay_ms": None if sched1 is None
                 else round((sched1[1] - sched0[1]) * 1e3, 1),
                 "voluntary_switches": ru1.ru_nvcsw - ru0.ru_nvcsw,
                 "involuntary_switches": ru1.ru_nivcsw - ru0.ru_nivcsw,
                 # None: no recorder recorded, so nothing timed the
                 # collector
                 "gc_ms": None if self._gc0 is None
                 else round((tracing.gc_seconds - self._gc0) * 1e3, 1)}
        extra = ""
        if self._detail is not None:
            try:
                extra = " [%s]" % self._detail()
            except Exception:   # noqa: BLE001 — best-effort log
                pass
        log.warning(
            "performance issue: %s took %.0f ms (on-CPU %.0f ms, "
            "run-delay %s ms, %d voluntary and %d involuntary context "
            "switches, gc %s ms)%s", self._name, dt * 1000, cpu * 1000,
            stall["run_delay_ms"], stall["voluntary_switches"],
            stall["involuntary_switches"], stall["gc_ms"], extra)
        if reg.metrics is not None:
            reg.metrics.new_counter("runtime.stall").inc()
        if tracing.ENABLED:
            tr = reg.tracer
            if tr is not None and tr.active:
                tr.instant("runtime.stall", stall)


class ZoneRegistry:
    def __init__(self):
        self._zones: Dict[str, _ZoneStats] = {}
        self._lock = threading.Lock()
        # the app's FlightRecorder (util/tracing.py), set by
        # Application: when it is recording, every zone ALSO emits a
        # begin/end span pair so the timeline gets the close phases,
        # completion jobs, bucket merges and verifier batches for free
        self.tracer = None
        # the app's MetricsRegistry, set by Application: where a scope
        # that overran is counted (`runtime.stall`)
        self.metrics = None

    def zone(self, name: str, targs: Optional[dict] = None,
             sink: Optional[dict] = None) -> _Zone:
        """Scoped timing zone (reference: Tracy ZoneScoped): wall and
        on-CPU seconds of the calling thread. `targs` are structured
        span args (ledger seq, tx count, …) recorded only while a trace
        is on — pass them pre-guarded by ``tracing.ENABLED`` so the
        disabled path allocates nothing. While one is on, the span's
        end carries its on-CPU time as `cpu_us`. With `sink`, the wall
        seconds are also added to `sink[name]`."""
        return _Zone(self, name, targs, sink)

    def add(self, name: str, seconds: float, count: int = 1,
            cpu_seconds: Optional[float] = None) -> None:
        """Report `count` hits of zone `name` that took `seconds` in
        all, for a site that measured the time itself: a per-item path
        that may not pay a context manager and this lock per item
        accumulates in plain attributes and reports once per close
        (`max` then sees the mean of the report), and a span that
        begins before its registry exists reports at its end.
        `cpu_seconds`: the on-CPU seconds of those same hits, where the
        site measured them for every one. Emits no recorder event."""
        if count <= 0:
            return
        with self._lock:
            st = self._zones.get(name)
            if st is None:
                st = self._zones[name] = _ZoneStats()
            st.count += count
            st.total += seconds
            if seconds / count > st.max:
                st.max = seconds / count
            if cpu_seconds is not None:
                st.cpu_count += count
                st.cpu += cpu_seconds
                if cpu_seconds / count > st.cpu_max:
                    st.cpu_max = cpu_seconds / count

    def zone_into(self, name: str, sink: Optional[dict] = None,
                  targs: Optional[dict] = None) -> _Zone:
        """A zone that ALSO accumulates its duration into `sink[name]`
        — the per-close phase breakdown the slow-execution log prints,
        so a 2.5 s stall names the guilty phase instead of one opaque
        number."""
        return self.zone(name, targs, sink)

    def log_slow_execution(self, name: str,
                           threshold_seconds: float = 1.0,
                           detail: Optional[Callable[[], str]] = None,
                           seq: Optional[int] = None,
                           sched0: Optional[Tuple[float, float]] = None
                           ) -> _SlowScope:
        """Warn when a scope overruns (reference:
        util/LogSlowExecution.h), and say what the thread did
        meanwhile: wall and on-CPU milliseconds, voluntary and
        involuntary context switches, the collector's milliseconds
        (while a recorder records) and, where the caller hands in its
        `thread_sched()` reading from the scope's start (`sched0`: a
        close has one), the run-delay. The overrun is counted
        (`runtime.stall`) and, while a recorder records, an instant
        `runtime.stall` carries the same numbers with `seq`. `detail`
        (evaluated only on overrun) appends a breakdown, e.g. the
        per-phase times of a slow close."""
        return _SlowScope(self, name, threshold_seconds, detail, seq,
                          sched0)

    def _publish_gc(self) -> None:
        # what the recorder's collector callback has counted and not
        # yet reported (util/tracing.py): the callback may take no lock
        tr = self.tracer
        if tr is not None:
            tr.publish_gc()

    def report(self) -> Dict[str, dict]:
        self._publish_gc()
        out: Dict[str, dict] = {}
        with self._lock:
            for name, st in sorted(self._zones.items()):
                out[name] = _entry(st.count, st.total, st.max)
                if st.cpu_count:
                    out[name]["cpu_ms"] = round(st.cpu * 1000, 3)
                    out[name + ON_CPU] = _entry(st.cpu_count, st.cpu,
                                                st.cpu_max)
        return out

    def reset(self) -> None:
        self._publish_gc()
        with self._lock:
            self._zones.clear()


# process-default registry for app-less contexts
default_registry = ZoneRegistry()


def zone(name: str):
    return default_registry.zone(name)


def log_slow_execution(name: str, threshold_seconds: float = 1.0):
    return default_registry.log_slow_execution(name, threshold_seconds)


def zone_report() -> Dict[str, dict]:
    return default_registry.report()


def reset_zones() -> None:
    default_registry.reset()
