"""From the JAX profiler's trace of the measuring process to numbers.

`Tracer` starts and stops the profiler round the window (Python call
tracing off: the program is a Python application and would drown the
trace) and drops one marker annotation that carries the host's
`time.perf_counter`, so device events and the program's FlightRecorder
spans can be put on one clock. `DeviceTrace` is the reduction: which
intervals an operation ran on each device, the XLA modules with their
device durations, self time per operation, and the idle gaps.

Layout of a TPU trace as this JAX writes it (looked at by hand, PR 24):
planes `/device:TPU:<n>` with lines `XLA Modules` (one event per
program run, named `jit_<function>(<fingerprint>)`), `XLA Ops` (one
event per HLO operation, nested: a `while` spans its body's operations)
and `Async XLA Ops` (copies in flight); plane `/host:CPU` with a line
`python` that holds the annotations. Times are nanoseconds from the
start of the trace, the same zero on every plane.
"""

import glob
import os
import re
import shutil
import time

MARKER = "benchmark.marker"
DEVICE_PLANE = "/device:"
SKIP_PLANES = ("/device:CUSTOM",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP_KIND = re.compile(r"\s([a-z][\w\-]*)\(")


def merge_intervals(intervals) -> list:
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events) -> dict:
    """{name: seconds not covered by nested events} for events
    [(start, end, name)] of one line, where a later event that starts
    before an earlier one ends is nested in it."""
    out = {}
    stack = []                      # [end, name, self seconds]

    def pop():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + own

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        pop()
    return out


def short_op_name(name: str) -> str:
    """`%fusion.3 = s32[...] fusion(...)` -> `%fusion.3 fusion`."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    kind = _OP_KIND.search(rest)
    return (head + " " + (kind.group(1) if kind else "")).strip()[:80]


class DeviceTrace:
    """Reduction of one xplane file. All times in seconds on the
    host's `time.perf_counter` (via the marker)."""

    def __init__(self, planes, t_start: float, t_stop: float, chips: int):
        """`planes`: [(plane name, [(line name, [(start_ns, dur_ns,
        name, stats)])])] — what `read_xplane` yields."""
        self.t_start, self.t_stop = t_start, t_stop
        self.window_s = t_stop - t_start
        marker = None
        for pname, lines in planes:
            if pname.startswith(DEVICE_PLANE):
                continue
            for _, events in lines:
                for s, d, name, stats in events:
                    if name == MARKER and "t" in stats:
                        marker = (s, float(stats["t"]))
        if marker is None:
            raise RuntimeError("the trace holds no marker annotation")
        self._ns0, self._t0 = marker

        self.devices = []          # per device plane: dict
        for pname, lines in planes:
            if not pname.startswith(DEVICE_PLANE) or \
                    pname.startswith(SKIP_PLANES):
                continue
            by_line = {ln: ev for ln, ev in lines}
            ops = by_line.get(OPS_LINE) or []
            modules = by_line.get(MODULES_LINE) or []
            basis = ops or modules
            if not basis:
                continue
            dev = {
                "plane": pname,
                "busy": merge_intervals(
                    [self._t(s), self._t(s + d)] for s, d, _, _ in basis),
                "modules": [(self._t(s), self._t(s + d), name)
                            for s, d, name, _ in modules],
                "ops": [(self._t(s), self._t(s + d), name)
                        for s, d, name, _ in ops],
            }
            self.devices.append(dev)
        self.on_accelerator = bool(self.devices)
        n = max(chips, 1)
        self.busy_s = sum(
            covered(clip(d["busy"], t_start, t_stop))
            for d in self.devices) / n

    def _t(self, ns: float) -> float:
        return self._t0 + (ns - self._ns0) / 1e9

    # ----------------------------------------------------- reductions --
    def busy_in(self, lo: float, hi: float) -> float:
        """Seconds an operation ran, averaged over the device planes,
        inside [lo, hi]."""
        if not self.devices:
            return 0.0
        return sum(covered(clip(d["busy"], lo, hi))
                   for d in self.devices) / len(self.devices)

    def module_runs(self, program: str, lo: float, hi: float) -> list:
        """Device seconds of each run of the XLA module `jit_<program>`
        that lies wholly inside [lo, hi]."""
        want = "jit_" + program
        return [e - s for d in self.devices for s, e, name in d["modules"]
                if name.split("(", 1)[0] == want and s >= lo and e <= hi]

    def top_ops(self, n: int = 10) -> list:
        total = {}
        for d in self.devices:
            events = d["ops"] or d["modules"]
            for name, sec in self_times(events).items():
                key = short_op_name(name)
                total[key] = total.get(key, 0.0) + sec
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec] for name, sec in top]

    def idle_gaps(self, least: float = 1e-3) -> list:
        """[(start, end)] of at least `least` seconds inside the traced
        window in which no operation ran on any device, longest
        first."""
        busy = merge_intervals(
            iv for d in self.devices for iv in d["busy"])
        busy = clip(busy, self.t_start, self.t_stop)
        gaps, at = [], self.t_start
        for s, e in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if self.t_stop > at:
            gaps.append((at, self.t_stop))
        return sorted((g for g in gaps if g[1] - g[0] >= least),
                      key=lambda g: g[0] - g[1])

    def breakdown(self, cell, n: int = 10) -> dict:
        from benchmark.harness.hostspans import HostTimeline
        timeline = HostTimeline(cell)
        gaps = []
        for s, e in self.idle_gaps()[:n]:
            gaps.append([timeline.name_of(s, e), e - s])
        return {"device_ops": self.top_ops(n), "idle_gaps": gaps}


def read_xplane(path: str) -> list:
    """The planes of an `.xplane.pb` as plain lists (see DeviceTrace)."""
    from jax.profiler import ProfileData
    import warnings
    planes = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            is_dev = plane.name.startswith(DEVICE_PLANE)
            lines = []
            for line in plane.lines:
                events = []
                for ev in line.events:
                    if is_dev:
                        events.append((ev.start_ns, ev.duration_ns,
                                       ev.name, None))
                    elif ev.name == MARKER:
                        events.append((ev.start_ns, ev.duration_ns,
                                       ev.name, dict(ev.stats)))
                lines.append((line.name, events))
            planes.append((plane.name, lines))
    return planes


class Tracer:
    def __init__(self, directory: str):
        self.dir = directory
        self.running = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True
        self.t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARKER, t=repr(self.t_start)):
            pass

    def stop(self, chips: int) -> DeviceTrace:
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return DeviceTrace(read_xplane(found[0]), self.t_start,
                           self.t_stop, chips)

    def abandon(self) -> None:
        if self.running:
            import jax
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass
            self.running = False
