"""Device time of the `verify_kernel_msg32` program per real signature
(us): device durations of the module's runs that lie inside the window,
from the profiler's trace, over the signatures those runs carried."""

PROGRAM = "verify_kernel_msg32"


def read(cell):
    trace = cell.device_trace
    if trace is None or not trace.on_accelerator:
        return None
    runs = trace.module_runs(PROGRAM, *cell.window)
    n, batch = cell.counters.get("crypto.verify.dispatch.batch", (0, 0.0))
    if not runs or not n:
        return None
    return sum(runs) / (len(runs) * batch / n) * 1e6
