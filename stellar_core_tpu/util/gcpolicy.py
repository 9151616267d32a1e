"""CPython GC policy: keep the collector off the close path.

**Full passes** (ISSUE 12, the TPSMT leg): automatic generation-2
collections scanned the whole multi-app heap for 50-1600 ms apiece —
16.2 s of a 50 s measured window — and freed approximately nothing
(0-710 objects per pass), because the live set (ledger state, XDR type
tables, bucket indexes) only grows. Those pauses landed inside
`closeLedger` (the 3 s `fees`-phase outliers in the close-phase
report) and inside the overlay crank, where they also expire
single-flight FLOOD_DEMANDs that were answered promptly.

**Young passes** (ISSUE 38). CPython's trigger for generation 0 is the
net growth of tracked objects since the last pass, 700 by default. A
close of 1,000 payments grows the heap by ~120 tracked objects a
transaction and gives ~85 of them back when its tail ends, one ledger
later, by reference count: nothing a close makes is cyclic. So at 700
the passes walk a ledger's frames, results, meta and entry copies
*while they are alive*, once in generation 0 and once more in
generation 1, and promote them to generation 2, where they die
unobserved. One `TPU v5 lite` host, recorder on, `runtime.gc` zone:

- (chip runs of PR 37) `catchup-pay1000.replay`: 22,927 passes in a
  44.53 s window (185 a close), 4.74 s of it (10.6 %), 3.21 s in 1,912
  passes of generation 1 at 1.68 ms each; `multisig-dense.dense-replay`
  7.86 s of 51.16 (15.4 %); `multisig-range.range-replay` 8.77 s of
  55.64 (15.8 %); a live transaction 28.6 us of 531.
- (chip runs of PR 38, call A, one traced replay of 126 closes and
  123,061 transactions a rung, seed 7380021001) the yield at 700:
  4,158 objects freed in 25,247 passes, 0.16 a pass, 33 a ledger. The
  rungs, as passes of generation 0 + 1 | seconds in them | longest
  pass | the deferred bill (`gc.collect(1)` right after the window) |
  `catchup_ledgers_per_s`: 700: 23,144 + 2,103 | 8.93 | 33 ms | 0.00 |
  2.197; 65,536: 162 + 14 | 8.41 | 390 ms | 0.14 | 2.077 (one cold
  walk of a live ledger a close: worse than the tree); 1,048,576:
  5 + 0 | 1.98 | 922 ms | 1.29 | 2.307; 4,194,304: 1 + 0 | 1.15 |
  1,154 ms | 1.42 | 2.337 (two checkpoints in flight cross it once);
  2**30: none | 0 | - | 1.52 | 2.375. Peak resident memory of the
  window within 0.4 % on every rung (17.62-17.68 GB sampled): the
  passes free nothing, so not running them keeps nothing more.
- (the same call, `standalone-pay1000.closed`, 60 closes of 1,000
  payments, two seeds a rung) 65,536 puts a pass of over 30 ms inside
  32 and 10 of 62 closes (longest 196 ms), 1,048,576 one of 387 ms
  inside one close, the two rungs above it none; after the window the
  young heap holds 1,678,990 tracked objects (27,983 a ledger: bucket
  entries, results, stamps; state, not garbage) and the bill for
  walking them once is 0.69-0.75 s.

ISSUE 38's rule (lowest `gc_us_per_tx` plus deferred bill in the range
replay; struck: a pass of over 30 ms inside more than one live close
in twenty, or peak resident memory over 1.1 times the tree's) picked
2**30: the young collector never comes on a count of allocations, and
cycles wait for the two passes somebody asks for. PERF.md section 6
(PR 38) has every rung's line.

Policy (process-wide, installed once by the first Application):

- generation 0's threshold is `YOUNG_THRESHOLD`, so no young pass
  falls inside a close; what is cyclic (33 objects a replayed ledger)
  is reclaimed by `maintenance_collect()` and `teardown_collect()`;
- the startup heap is frozen (`gc.freeze`) into the permanent
  generation so no future full collection re-walks imports, XDR type
  tables and constant pools;
- automatic gen2 collection is pushed out (threshold 1e6 instead of
  the heuristic) — a full scan may only run when something asks for
  it deliberately;
- `maintenance_collect()` runs the explicit full pass from the
  Maintainer's cron (reference: Maintainer::performMaintenance
  cadence, i.e. history-GC time, never close time) so reference
  cycles from long runs still get reclaimed.
"""

from __future__ import annotations

import gc

from .logging import get_logger

log = get_logger("Perf")

# generation 0's threshold, in net allocations of tracked objects since
# the last pass: more than a process can hold (the rule and the rungs
# that lost are in the docstring). `tracing`'s `runtime.gc.collected`
# over the passes counted is the yield that says when to look again
YOUNG_THRESHOLD = 1 << 30

_installed = False


def install() -> bool:
    """Idempotent, process-wide. Returns True on the first install."""
    global _installed
    if _installed:
        return False
    _installed = True
    gc.collect()
    # everything alive at first-app construction is effectively
    # immortal (modules, XDR metaclass tables, jitted callables):
    # keep gen2 from ever re-scanning it
    gc.freeze()
    gc.set_threshold(YOUNG_THRESHOLD, gc.get_threshold()[1], 1_000_000)
    log.debug("gc policy installed: startup heap frozen, automatic "
              "full collections disabled, young threshold %d",
              YOUNG_THRESHOLD)
    return True


def maintenance_collect() -> int:
    """Explicit full collection for maintenance windows (the sanctioned
    full-heap pass once `install` ran — the permanent generation stays
    excluded, so this scans only what the process allocated since
    startup). No re-freeze: freezing live node state (entry caches,
    flow-control queues) would make it immortal when it later becomes
    garbage. Returns the number of collected objects."""
    return gc.collect()


# reclaim cadence for app teardown: a full pass per shutdown measured
# ~150s across the 900-test suite (hundreds of app churns), while the
# leak window of deferring is a handful of dead app graphs — collect
# on the Nth teardown, not every one
TEARDOWN_COLLECT_EVERY = 8
_teardowns = 0


def teardown_collect(force: bool = False) -> int:
    """Application.shutdown hook: with automatic full collections
    disabled, torn-down apps' reference cycles (app↔herder↔overlay
    back-pointers) must be reclaimed HERE or a process that builds
    many short-lived apps — the test suite, multi-leg bench runs —
    accumulates every dead app until exit. Throttled to every
    `TEARDOWN_COLLECT_EVERY`th shutdown: the deferred window is a few
    dead app graphs, the saving is one full heap scan per test."""
    global _teardowns
    _teardowns += 1
    if not force and _teardowns % TEARDOWN_COLLECT_EVERY:
        return 0
    return gc.collect()
