"""Share of the measured window in which no operation ran on the device
(%): 25 runs of 256 lanes a flooded ledger, some 300 a window. The
profiler's device buffer holds the operations of the last ~50 runs of
such a window and drops the older ones, so the union of the intervals
it kept (`device_idle_share.live`'s reading) would count a sixth of the
device's work. Where the trace kept fewer runs of `verify_kernel_msg32`
inside the window than the program dispatched
(`crypto.verify.dispatch.batch`'s count), busy time is the mean device
duration of the runs it kept times the runs dispatched: a run's shape
is the same all through the window, and nothing else runs on the
device. The union of a cut trace is not to be trusted either way: it
read 0.19 and 0.66 s where the runs make 1.2, and 2.30 s once (an
operation whose end was dropped spans what follows it). Where the trace
kept every run, the union as everywhere."""

PROGRAM = "verify_kernel_msg32"


def read(cell):
    trace = cell.device_trace
    if trace is None or not trace.on_accelerator:
        return None
    lo, hi = cell.window
    busy = trace.busy_in(lo, hi)
    kept = trace.module_runs(PROGRAM, lo, hi)
    runs, _ = cell.counters.get("crypto.verify.dispatch.batch", (0, 0.0))
    if kept and runs > len(kept):
        cell.note(f"the trace kept {len(kept)} of {runs} device runs of "
                  f"the window: busy time {busy:.4f} s by the union of "
                  f"what it kept, {sum(kept) / len(kept) * runs:.4f} s by "
                  "the mean run times the runs dispatched")
        busy = sum(kept) / len(kept) * runs
    return 100.0 * (1.0 - busy / (hi - lo))
