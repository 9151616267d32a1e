"""Share of the measured window in which no operation ran on the device
(%): 100 x (1 - union of device-operation intervals / window), from the
profiler's trace."""


def read(cell):
    trace = cell.device_trace
    if trace is None or not trace.on_accelerator:
        return None
    lo, hi = cell.window
    return 100.0 * (1.0 - trace.busy_in(lo, hi) / (hi - lo))
