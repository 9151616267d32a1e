#!/usr/bin/env python3
"""Summarize or diff flight-recorder traces (reference analogue:
scripts/DiffTracyCSV.py, which diffs two Tracy capture CSVs —
scripts/README.md:14-19; here over Chrome trace-event JSON).

Inputs are trace files from the admin API:

    curl -s 'localhost:11626/starttrace'
    ... run a workload ...
    curl -s 'localhost:11626/dumptrace?path=/tmp/run.json'
    python scripts/trace_report.py /tmp/run.json
    python scripts/trace_report.py /tmp/run.json /tmp/other.json

With one trace: top zones by total time and on-CPU time (`cpu_us` on
a span's end: wall less on-CPU is what its thread stood still), the
ledger-close critical
path (per-phase breakdown of every ledger.close.* span), and
barrier-wait gaps (time closes spent blocked on the completion
worker). With two: a per-zone count/total/mean delta table, sorted so
regressions stand out the same way DiffTracyCSV's diffs do.

Cluster views over a MERGED trace (Simulation.merged_trace or
Cluster.merged_trace, one process lane per node):

    python scripts/trace_report.py merged.json --slots
    python scripts/trace_report.py merged.json --flood

`--slots` tabulates per-slot SCP phase latencies (nominate / prepare /
confirm spans per node lane) with slowest-node attribution per slot;
`--flood` analyzes the hash-keyed propagation instants: hop-count
distribution, duplicate-delivery ratio, and per-link propagation
latency p50/p99.
"""

import argparse
import json
import sys
from collections import defaultdict


def load_spans(path):
    """Pair B/E events per (pid, tid) into [(name, start_us, dur_us)].
    Also returns instant/async event counts by name for the summary."""
    with open(path) as f:
        doc = json.load(f)
    events = doc if isinstance(doc, list) \
        else doc.get("traceEvents", [])
    spans = []
    other = defaultdict(int)
    stacks = defaultdict(list)
    for ev in events:
        ph = ev.get("ph")
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks[key].append(ev)
        elif ph == "E":
            if stacks[key]:
                b = stacks[key].pop()
                args = b.get("args") or {}
                if "cpu_us" in (ev.get("args") or {}):
                    args = dict(args, cpu_us=ev["args"]["cpu_us"])
                spans.append((b["name"], b["ts"], ev["ts"] - b["ts"], args))
        elif ph in ("i", "b", "e"):
            other[f"{ph}:{ev.get('name')}"] += 1
    return spans, other


def aggregate(spans):
    """name -> {count, total_us, max_us, cpu_us}."""
    agg = {}
    for name, _ts, dur, args in spans:
        st = agg.setdefault(name, {"count": 0, "total_us": 0.0,
                                   "max_us": 0.0, "cpu_us": 0.0})
        st["count"] += 1
        st["total_us"] += dur
        st["max_us"] = max(st["max_us"], dur)
        st["cpu_us"] += args.get("cpu_us", 0.0)
    return agg


def _fmt_ms(us):
    return "%.2f" % (us / 1000.0)


def summarize(path, top):
    spans, other = load_spans(path)
    agg = aggregate(spans)
    print(f"== {path}: {len(spans)} spans, {len(agg)} zones ==")
    print(f"{'zone':42} {'count':>8} {'total_ms':>12} {'mean_ms':>10} "
          f"{'max_ms':>10} {'cpu_ms':>12}")
    for name, st in sorted(agg.items(),
                           key=lambda kv: -kv[1]["total_us"])[:top]:
        print(f"{name:42} {st['count']:>8} "
              f"{_fmt_ms(st['total_us']):>12} "
              f"{_fmt_ms(st['total_us'] / st['count']):>10} "
              f"{_fmt_ms(st['max_us']):>10} "
              f"{_fmt_ms(st['cpu_us']):>12}")

    # ---- ledger-close critical path: per-phase share of closeLedger
    closes = [s for s in spans if s[0] == "ledger.closeLedger"]
    phases = {n: st for n, st in agg.items()
              if n.startswith("ledger.close.")}
    if closes:
        total_close = sum(s[2] for s in closes)
        print(f"\n-- close critical path ({len(closes)} closes, "
              f"total {_fmt_ms(total_close)} ms) --")
        for name, st in sorted(phases.items(),
                               key=lambda kv: -kv[1]["total_us"]):
            share = 100.0 * st["total_us"] / max(1e-9, total_close)
            print(f"{name:42} {_fmt_ms(st['total_us']):>12} "
                  f"{share:>6.1f}%  max {_fmt_ms(st['max_us'])}")

    # ---- barrier-wait gaps: time the close path spent blocked on the
    # completion worker (PR 1's pipeline seam) — nonzero means the
    # deferred tail is slower than the consensus-critical segment
    wait = agg.get("ledger.close.completeWait")
    if wait:
        print(f"\n-- barrier-wait gaps (ledger.close.completeWait) --")
        print(f"count {wait['count']}, total {_fmt_ms(wait['total_us'])}"
              f" ms, max {_fmt_ms(wait['max_us'])} ms")

    if other:
        print("\n-- instant / async events --")
        for name, n in sorted(other.items(), key=lambda kv: -kv[1])[:top]:
            print(f"{name:42} {n:>8}")


def _load_events(path):
    """Raw event list + pid -> process_name (node label) map."""
    with open(path) as f:
        doc = json.load(f)
    events = doc if isinstance(doc, list) \
        else doc.get("traceEvents", [])
    labels = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            labels[ev["pid"]] = ev.get("args", {}).get("name",
                                                       str(ev["pid"]))
    return events, labels


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


def report_slots(path):
    """Per-slot SCP phase latency table over a merged cluster trace:
    for every slot, each phase's mean/max across node lanes, plus
    which node finished the slot last (slowest-node attribution).
    Returns the table rows for programmatic use."""
    events, labels = _load_events(path)
    # (pid, slot) -> {phase: begin_ts}; async b/e pairs per node lane
    begins = {}
    durs = defaultdict(dict)     # (pid, slot) -> {phase: dur_us}
    extern = {}                  # (pid, slot) -> externalize ts
    for ev in events:
        name = ev.get("name", "") or ""
        if ev.get("ph") in ("b", "e") and name.startswith("scp.slot."):
            phase = name.rsplit(".", 1)[1]
            slot = (ev.get("args") or {}).get("slot")
            if slot is None:
                continue
            key = (ev["pid"], slot)
            if ev["ph"] == "b":
                begins[(key, phase)] = ev["ts"]
            else:
                t0 = begins.pop((key, phase), None)
                if t0 is not None:
                    durs[key][phase] = ev["ts"] - t0
        elif ev.get("ph") == "i" and name == "scp.externalize":
            slot = (ev.get("args") or {}).get("slot")
            if slot is not None:
                extern[(ev["pid"], slot)] = ev["ts"]
    slots = sorted({s for _, s in durs} | {s for _, s in extern})
    rows = []
    print(f"== {path}: slot timelines across "
          f"{len(labels) or 'unknown'} node lanes ==")
    print(f"{'slot':>6} {'nominate ms':>12} {'prepare ms':>12} "
          f"{'confirm ms':>12} {'slowest node':>14} {'spread ms':>10}")
    for slot in slots:
        per_phase = {}
        for phase in ("nominate", "prepare", "confirm"):
            vals = [d[phase] for (pid, s), d in durs.items()
                    if s == slot and phase in d]
            per_phase[phase] = (sum(vals) / len(vals) if vals else 0.0,
                                max(vals) if vals else 0.0)
        ext = {pid: ts for (pid, s), ts in extern.items() if s == slot}
        slowest = spread = None
        if ext:
            slow_pid = max(ext, key=ext.get)
            slowest = labels.get(slow_pid, str(slow_pid))
            spread = max(ext.values()) - min(ext.values())
        row = {"slot": slot,
               **{p + "_ms": round(per_phase[p][0] / 1000.0, 3)
                  for p in per_phase},
               "slowest": slowest, "spread_us": spread}
        rows.append(row)
        print(f"{slot:>6} "
              f"{_fmt_ms(per_phase['nominate'][0]):>12} "
              f"{_fmt_ms(per_phase['prepare'][0]):>12} "
              f"{_fmt_ms(per_phase['confirm'][0]):>12} "
              f"{(slowest or '-'):>14} "
              f"{_fmt_ms(spread) if spread is not None else '-':>10}")
    if not rows:
        print("(no scp.slot.* phase spans — record with tracing on "
              "and merge with Simulation.merged_trace)")
    return rows


def report_flood(path):
    """Flood-propagation analytics over a merged cluster trace: for
    every hash-keyed message, how many node lanes it reached (hop
    count), how many deliveries were redundant, and per-link
    propagation latency p50/p99 (send instant on the sender lane →
    recv instant on the receiver lane). Returns the summary dict."""
    events, labels = _load_events(path)
    label_to_pid = {v: k for k, v in labels.items()}
    sends = defaultdict(list)    # hash -> [(ts, pid)]
    recvs = defaultdict(list)    # hash -> [(ts, pid, from_label, dup)]
    demands_sent = demand_retries = 0
    tx_recvs = tx_dups = 0
    for ev in events:
        if ev.get("ph") != "i":
            continue
        args = ev.get("args") or {}
        if ev.get("name") == "flood.demand":
            # single-flight demand instants (ISSUE 12): n = hashes in
            # the FLOOD_DEMAND batch, retry = a timeout rotation
            n = args.get("n", 0)
            demands_sent += n
            if args.get("retry"):
                demand_retries += n
            continue
        h = args.get("hash")
        if not h:
            continue
        if ev.get("name") == "flood.send":
            sends[h].append((ev["ts"], ev["pid"]))
        elif ev.get("name") == "flood.recv":
            recvs[h].append((ev["ts"], ev["pid"], args.get("from"),
                             bool(args.get("dup"))))
            if args.get("type") == "TRANSACTION":
                tx_recvs += 1
                if args.get("dup"):
                    tx_dups += 1
    hop_hist = defaultdict(int)  # nodes reached -> message count
    total_recvs = dup_recvs = 0
    link_lat = defaultdict(list)  # (from_label, to_label) -> [us]
    for h, rs in recvs.items():
        reached = {pid for _, pid, _, _ in rs}
        hop_hist[len(reached)] += 1
        for ts, pid, frm, dup in rs:
            total_recvs += 1
            if dup:
                dup_recvs += 1
            # pair with the most recent earlier send on the sender lane
            spid = label_to_pid.get(frm)
            if spid is None:
                continue
            cand = [t for t, p in sends.get(h, ()) if p == spid
                    and t <= ts]
            if cand:
                link_lat[(frm, labels.get(pid, str(pid)))].append(
                    ts - max(cand))
    unique = len(recvs)
    # demand single-flight efficiency (ISSUE 12): how close pull-mode
    # fetching runs to one demand per unique tx body. >1 demand per
    # unique body = retries/rotations; duplicate bodies despite
    # single-flight = unsolicited pushes or races the table can't see
    unique_tx_bodies = max(0, tx_recvs - tx_dups)
    summary = {
        "messages": unique,
        "recvs": total_recvs,
        "duplicates": dup_recvs,
        "duplicate_ratio": round(dup_recvs / max(1, total_recvs -
                                                 dup_recvs), 4),
        "hop_histogram": dict(sorted(hop_hist.items())),
        "demand": {
            "demands_sent": demands_sent,
            "demand_retries": demand_retries,
            "tx_bodies": tx_recvs,
            "tx_duplicates": tx_dups,
            # None, not 0.0, when no unique body ever arrived: demands
            # with zero yield is the pathology this ratio exists to
            # expose, and 0.0 would display it as better-than-perfect
            "demands_per_unique_body": round(
                demands_sent / unique_tx_bodies, 4)
            if unique_tx_bodies else (None if demands_sent else 0.0),
        },
        "links": {},
    }
    print(f"== {path}: flood propagation, {unique} hash-keyed "
          f"messages, {total_recvs} deliveries ==")
    print(f"duplicate deliveries: {dup_recvs} "
          f"(ratio {summary['duplicate_ratio']})")
    if demands_sent:
        print(f"demand single-flight: {demands_sent} demanded "
              f"({demand_retries} retried), {tx_recvs} tx bodies "
              f"({tx_dups} duplicate) -> "
              f"{summary['demand']['demands_per_unique_body']} "
              f"demands per unique body")
    print("hop-count distribution (nodes reached -> messages):")
    for hops, n in sorted(hop_hist.items()):
        print(f"  {hops:>3} nodes: {n}")
    if link_lat:
        print(f"\n{'link':30} {'n':>6} {'p50 ms':>10} {'p99 ms':>10}")
        for (frm, to), vals in sorted(link_lat.items()):
            vals.sort()
            p50, p99 = _pctl(vals, 0.5), _pctl(vals, 0.99)
            summary["links"][f"{frm}->{to}"] = {
                "n": len(vals), "p50_ms": round(p50 / 1000.0, 3),
                "p99_ms": round(p99 / 1000.0, 3)}
            print(f"{frm + ' -> ' + to:30} {len(vals):>6} "
                  f"{_fmt_ms(p50):>10} {_fmt_ms(p99):>10}")
    if not unique:
        print("(no flood.send/flood.recv instants — record with "
              "tracing on during flood traffic)")
    return summary


def diff(path_a, path_b, top, min_delta_ms):
    agg_a = aggregate(load_spans(path_a)[0])
    agg_b = aggregate(load_spans(path_b)[0])
    rows = []
    for name in sorted(set(agg_a) | set(agg_b)):
        a = agg_a.get(name, {"count": 0, "total_us": 0.0})
        b = agg_b.get(name, {"count": 0, "total_us": 0.0})
        d_total = b["total_us"] - a["total_us"]
        if abs(d_total) / 1000.0 < min_delta_ms:
            continue
        mean_a = a["total_us"] / a["count"] if a["count"] else 0.0
        mean_b = b["total_us"] / b["count"] if b["count"] else 0.0
        rows.append((name, b["count"] - a["count"], d_total,
                     mean_b - mean_a))
    rows.sort(key=lambda r: -abs(r[2]))
    print(f"== {path_a} -> {path_b} ==")
    print(f"{'zone':42} {'Δcount':>8} {'Δtotal_ms':>12} {'Δmean_ms':>10}")
    for name, dc, dt, dm in rows[:top]:
        print(f"{name:42} {dc:>+8} {'%+.2f' % (dt / 1000.0):>12} "
              f"{'%+.2f' % (dm / 1000.0):>10}")


def main() -> int:
    # reports pipe into `head`/`grep` routinely; die silently on a
    # closed pipe like every other line-oriented CLI tool
    import signal
    try:
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    except (AttributeError, ValueError):
        pass
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome trace-event JSON file")
    ap.add_argument("other", nargs="?",
                    help="second trace: print a zone-delta diff")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--min-delta-ms", type=float, default=0.0,
                    help="diff mode: hide zones below this |Δtotal|")
    ap.add_argument("--slots", action="store_true",
                    help="per-slot SCP phase latency table with "
                         "slowest-node attribution (merged trace)")
    ap.add_argument("--flood", action="store_true",
                    help="flood hop-count distribution, duplicate "
                         "ratio, per-link propagation p50/p99 "
                         "(merged trace)")
    args = ap.parse_args()
    if args.slots or args.flood:
        if args.other:
            ap.error("--slots/--flood analyze ONE trace; "
                     "a second positional is diff mode only")
        if args.slots:
            report_slots(args.trace)
        if args.flood:
            report_flood(args.trace)
        return 0
    if args.other:
        diff(args.trace, args.other, args.top, args.min_delta_ms)
    else:
        summarize(args.trace, args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
