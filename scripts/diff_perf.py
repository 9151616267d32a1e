#!/usr/bin/env python3
"""Diff two perf-zone reports (reference analogue: scripts/DiffTracyCSV.py,
which diffs two Tracy capture CSVs — scripts/README.md:14-19).

Inputs are JSON files saved from the admin API's `perf` route, e.g.

    curl -s localhost:11626/perf > before.json
    ... run a workload ...
    curl -s localhost:11626/perf > after.json
    python scripts/diff_perf.py before.json after.json [--sort total]

Prints a per-zone table of count/total/mean deltas, sorted by the chosen
column's delta (default: total_ms), so regressions stand out the same
way DiffTracyCSV's execution-time diffs do. A zone's on-CPU milliseconds
(`cpu_ms`) are a column of its row; the derived `<zone>.onCpu` entries
that carry the same number for the benchmark's readers are not zones
and get no row.
"""

import argparse
import json
import sys


def load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return doc.get("perf", doc)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("before")
    ap.add_argument("after")
    ap.add_argument("--sort", choices=("total", "mean", "count"),
                    default="total")
    ap.add_argument("--min-delta-ms", type=float, default=0.0,
                    help="hide zones whose |total delta| is below this")
    args = ap.parse_args()

    before = load(args.before)
    after = load(args.after)
    names = sorted(n for n in set(before) | set(after)
                   if not n.endswith(".onCpu"))
    key = {"total": "total_ms", "mean": "mean_ms", "count": "count"}[
        args.sort]

    rows = []
    for name in names:
        b = before.get(name, {})
        a = after.get(name, {})
        d_count = a.get("count", 0) - b.get("count", 0)
        d_total = a.get("total_ms", 0.0) - b.get("total_ms", 0.0)
        d_mean = a.get("mean_ms", 0.0) - b.get("mean_ms", 0.0)
        d_cpu = a.get("cpu_ms", 0.0) - b.get("cpu_ms", 0.0)
        if abs(d_total) < args.min_delta_ms:
            continue
        rows.append((name, d_count, d_total, d_mean,
                     a.get("total_ms", 0.0), d_cpu))

    sort_idx = {"count": 1, "total": 2, "mean": 3}[args.sort]
    rows.sort(key=lambda r: -abs(r[sort_idx]))

    print(f"{'zone':40} {'Δcount':>10} {'Δtotal_ms':>12} "
          f"{'Δmean_ms':>10} {'after_total':>12} {'Δcpu_ms':>12}")
    for name, dc, dt, dm, at, dcpu in rows:
        print(f"{name:40} {dc:>+10d} {dt:>+12.3f} {dm:>+10.3f} "
              f"{at:>12.3f} {dcpu:>+12.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
