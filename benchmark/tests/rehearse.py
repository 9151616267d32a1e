"""Build a benchmark root that holds everything of this one plus what
`tests/data/added/` adds — files and entries only, no edit of a file
that is there — and run one of its cells on whatever device JAX has,
skipping only the harness's look for a chip.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py \
        --workload tiny-standalone.tiny-closed --seed 7 --seconds 3 --trace 1

This is the CPU rehearsal of both cells at tiny size; what it prints
names the device it ran on and carries no device metric.
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
ADDED = os.path.join(HERE, "data", "added")


def make_root(tmp: str) -> str:
    """`tmp` becomes a root with BENCHMARK.json and benchmark/: copies
    of the real ones, then the added files and entries on top."""
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {os.path.relpath(os.path.join(d, f), tmp)
              for d, _, fs in os.walk(tmp) for f in fs}
    for sub in ("configs", "traffic", "layer_metrics"):
        for name in os.listdir(os.path.join(ADDED, sub)):
            dst = os.path.join(tmp, "benchmark", sub, name)
            if os.path.relpath(dst, tmp) in before:
                raise AssertionError(f"{dst} would replace a file")
            shutil.copy(os.path.join(ADDED, sub, name), dst)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(ADDED, "BENCHMARK.add.json")) as f:
        add = json.load(f)
    doc["configs"] += add["configs"]
    doc["workloads"] += add["workloads"]
    doc["per_layer"] += add["per_layer"]
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            more = add.get(group + "_workloads", {}).get(m["name"])
            if more:
                m["workloads"] = m["workloads"] + more
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return tmp


def rehearse(argv, tmp: str, out=sys.stdout, driver_hook=None) -> int:
    sys.path.insert(0, ROOT)
    from benchmark.harness.main import main
    return main(argv, t0=time.perf_counter(), root=make_root(tmp),
                require_chip=False, out=out, driver_hook=driver_hook)


if __name__ == "__main__":
    import tempfile
    with tempfile.TemporaryDirectory(prefix="bench-rehearse-") as tmp:
        sys.exit(rehearse(sys.argv[1:], tmp))
