"""What the host was doing while the device sat idle: the program's
FlightRecorder spans (zones of the thread that closes ledgers) and the
benchmark's own spans, on the host's `time.perf_counter`."""

import threading


class HostTimeline:
    def __init__(self, cell):
        # (start, end, name, depth): innermost span wins where several
        # are open, so depth breaks ties. Only the thread that drives
        # the nodes (it closes the ledgers) is read: the completion
        # worker's zones run beside it.
        self.tid = threading.get_ident()
        self.spans = []
        for name, s, e, _ in cell.spans.items:
            self.spans.append((s, e, name, 0))
        for rec in cell.recorders:
            self._from_recorder(rec)

    def _from_recorder(self, rec) -> None:
        open_by_tid = {}
        for ev in rec.to_chrome_trace()["traceEvents"]:
            ts = rec.t0 + ev.get("ts", 0.0) / 1e6
            if ev.get("tid") != self.tid:
                continue
            if ev["ph"] == "B":
                open_by_tid.setdefault(ev["tid"], []).append(
                    (ev["name"], ts))
            elif ev["ph"] == "E":
                stack = open_by_tid.get(ev["tid"])
                if stack:
                    name, began = stack.pop()
                    self.spans.append((began, ts, name, len(stack) + 1))

    def name_of(self, lo: float, hi: float) -> str:
        """The three spans that account for most of [lo, hi], counting
        each moment for the innermost span open in it, with shares."""
        cuts = sorted({lo, hi} | {t for s, e, _, _ in self.spans
                                  for t in (s, e) if lo < t < hi})
        share = {}
        live = [sp for sp in self.spans if sp[1] > lo and sp[0] < hi]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            best = None
            for s, e, name, depth in live:
                if s <= mid < e and (best is None or depth > best[1]):
                    best = (name, depth)
            key = best[0] if best else "(no span open)"
            share[key] = share.get(key, 0.0) + (b - a)
        top = sorted(share.items(), key=lambda kv: -kv[1])[:3]
        return ", ".join(f"{name} {100 * sec / (hi - lo):.0f}%"
                         for name, sec in top)
