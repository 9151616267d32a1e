"""Share of its roofline the `verify_kernel_msg32` program reaches (%):
the least time the chip could take for the batch shape (the larger of
the algorithm's operations over the peak rate and its bytes over the
peak bandwidth, both from `kernel_costs/verify_kernel_msg32.py` and
`peaks.json`) over the device time of the module's runs in the trace."""

PROGRAM = "verify_kernel_msg32"


def read(cell):
    trace = cell.device_trace
    if trace is None or not trace.on_accelerator:
        return None
    runs = trace.module_runs(PROGRAM, *cell.window)
    n, batch = cell.counters.get("crypto.verify.dispatch.batch", (0, 0.0))
    _, padding = cell.counters.get("crypto.verify.dispatch.padding",
                                   (0, 0.0))
    if not runs or not n:
        return None
    lanes = round((batch + padding) / n)
    cost = cell.spec.kernel_cost(PROGRAM)
    import jax
    peaks = cell.spec.peaks(jax.devices()[0].device_kind)
    least, bound = cost.least_seconds(lanes, peaks)
    cell.note(f"{PROGRAM} at {lanes} lanes: least time {least:.6f} s, "
              f"bound by {bound}; device time {sum(runs) / len(runs):.6f} s "
              f"a run over {len(runs)} runs")
    return 100.0 * least * len(runs) / sum(runs)
