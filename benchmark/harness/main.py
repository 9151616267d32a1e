"""One run of one cell: set-up, window, checks, result line."""

import argparse
import gc
import json
import os
import shutil
import sys
import time

from benchmark.harness.cell import Cell
from benchmark.harness.checks import Check
from benchmark.harness.spec import Spec


def _device_doc(devices, chips: int) -> dict:
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": chips,
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0)}


class CompileWatch:
    """When XLA compiled (or read a compiled program back): nothing may
    compile inside the measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.ends = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if name == self.EVENT:
            self.ends.append(time.perf_counter())

    def inside(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.ends if lo <= t <= hi)


def run_cell(spec: Spec, args, t0: float, require_chip: bool,
             out=sys.stdout, driver_hook=None) -> int:
    """`driver_hook(driver)` lets a test reach under the timed path
    after set-up (break a verifier, drop a transaction); the command
    never passes one."""
    wl = spec.workload(args.workload)
    os.environ.pop("JAX_LOG_COMPILES", None)
    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < wl["chips"]):
        print(f"benchmark: cell {wl['name']} needs {wl['chips']} TPU "
              f"chip(s); JAX found {len(devices)} x "
              f"{devices[0].platform}. No result.", file=sys.stderr)
        return 2
    config = spec.config(wl["config"])
    traffic = spec.traffic(wl["traffic"])
    workdir = os.path.join(spec.root, ".bench_work",
                           f"{wl['name']}-{args.seed}-{os.getpid()}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cell = Cell(wl["name"], config, traffic, args.seed, args.seconds,
                bool(args.trace), workdir, wl["chips"])
    cell.spec = spec
    driver = spec.generator(traffic["generator"]).Driver(cell)
    tracer = None
    compiles = CompileWatch()
    try:
        driver.setup()
        if driver_hook is not None:
            driver_hook(driver)
        if cell.trace:
            from benchmark.harness.trace import Tracer
            tracer = Tracer(os.path.join(workdir, "profile"))
        # what set-up built (signed envelopes, the archive's tuples) is
        # the generator's, not the program's: keep it out of every
        # collection the program runs inside the window
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t0
        if tracer is not None:
            tracer.start()
        driver.window(args.seconds)
        cell.window = (driver.t_start, driver.t_end)
        driver.after_window()
        if tracer is not None:
            cell.device_trace = tracer.stop(cell.chips)
        checks = driver.check()
        checks.append(Check("programs compiled inside the measured window",
                            compiles.inside(driver.t_start, driver.t_end),
                            0))
        end_to_end = dict(driver.end_to_end(), setup_s=setup_s)
        device = _device_doc(devices, wl["chips"])
        attempted, failed = driver.attempted, driver.failed
    finally:
        if tracer is not None:
            tracer.abandon()
        driver.close()
        shutil.rmtree(workdir, ignore_errors=True)

    correct = all(c.ok for c in checks)
    reports = [m["name"] for m in spec.metrics_for(
        "end_to_end", wl["name"], [])]
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in spec.doc[g]}
    e2e = {n: {"value": end_to_end[n], "unit": units[n]} for n in reports}
    doc = {"correct": correct, "attempted": attempted, "failed": failed}
    if not cell.trace:
        doc["metrics"] = e2e
    else:
        layer = {}
        for m in spec.metrics_for("per_layer", wl["name"], reports):
            value = spec.layer_reader(m["name"])(cell)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        doc["metrics"] = layer
        doc["end_to_end_while_traced"] = e2e
        doc["host_zones_s"] = [
            [z, round(sec, 4), n] for z, (n, sec) in sorted(
                cell.zones.items(), key=lambda kv: -kv[1][1])[:16]]
        tr = cell.device_trace
        if tr is not None and not tr.on_accelerator and not require_chip:
            tr = None       # a rehearsal: nothing under a device's name
        if tr is not None:
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            doc["breakdown"] = tr.breakdown(cell)
    for c in checks:
        print(c.line(), file=out)
    for n in cell.notes:
        print("note:", n, file=out)
    doc["device"] = device
    doc["workload"] = wl["name"]
    doc["seed"] = args.seed
    doc["window_s"] = driver.t_end - driver.t_start
    print(json.dumps(doc), file=out, flush=True)
    return 0


def main(argv, t0: float, root: str, require_chip: bool = True,
         out=sys.stdout, driver_hook=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(Spec.load(root), args, t0, require_chip, out,
                    driver_hook)
