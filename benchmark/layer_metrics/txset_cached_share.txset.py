"""Crypto layer: share of the validated sets' signatures that the verify
cache answered (%): `herder.txset.prevalidate.cached` over `.cached` +
`.dispatched` + `.fallback`. 0 in this cell (the cold case, `assumed`);
the axis of PERF.md's sweep. Nothing on a program without the
counters; 0 where no signature was counted."""


def read(cell):
    if "herder.txset.prevalidate.cached" not in cell.counters:
        return None
    cached, _ = cell.counters["herder.txset.prevalidate.cached"]
    dispatched, _ = cell.counters.get("herder.txset.prevalidate.dispatched",
                                      (0, 0.0))
    fallback, _ = cell.counters.get("herder.txset.prevalidate.fallback",
                                    (0, 0.0))
    total = cached + dispatched + fallback
    return 100.0 * cached / total if total else 0.0
