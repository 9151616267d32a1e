"""A batch larger than the largest compiled bucket, run as chunks of it.

Shared by the device verifier (`ops/verifier.py`) and the backend
supervisor, and jax-free like `shard_math.py`, because the supervisor
must stay importable without the device stack.

One XLA program exists per bucket size, and a lane is not the same
price on every rung: on a TPU v5e a full run reads 9.4 us a lane at
2,048 lanes, 9.8 at 4,096, 10.5 at 8,192, 20.6 at 16,384, 38.8 at
32,768 and 44.8 at 65,536 (PERF.md section 7). The ladder ends at the
cheapest rung, the larger where two are within 5 %: 4,096. A batch
beyond it runs as ceil(n / MAX_BUCKET) calls of that one program, in
the order given, the remainder padded into the same bucket (one shape,
no second compile), with at most MAX_CHUNKS_IN_FLIGHT of them
dispatched and not yet collected. Three, because the thread that
collects a chunk and dispatches the next shares the interpreter with
apply and comes back 25-70 ms after a chunk lands, where a run is 40
ms: with two in flight the device stands idle between the runs of a
batch (PERF.md section 6). A chunk's dispatch-to-collect stays near
three runs, 0.12 s, under a deadline that is set in seconds. A small
chunk is also an early one: a checkpoint's first verdicts are back
after one run, before apply has reached the ledgers they cover. Both
are constants, not config fields: they follow from the chip's ladder,
not from a deployment.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, List, Optional, Sequence, Tuple

from ..util.logging import get_logger

log = get_logger("Herder")

MAX_BUCKET = 4096
MAX_CHUNKS_IN_FLIGHT = 3


def chunk_bounds(n: int, size: int) -> List[Tuple[int, int]]:
    """[lo, hi) of each chunk of `n` items in chunks of `size`."""
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def chunks_of(handle, n: int):
    """Yield (lo, hi, verdicts) of each chunk of a collect callable for
    `n` tuples as it lands: `handle.chunks()` of a split batch, and one
    chunk, the whole result, of a callable that has none."""
    chunks = getattr(handle, "chunks", None)
    if chunks is None:
        yield 0, n, handle()
    else:
        yield from chunks()


class ChunkedCollect:
    """The collect callable of a batch that was split: called, it
    yields every verdict in the order given, like the callable of a
    batch that fits one bucket; `chunks()` hands out each chunk's
    verdicts as it lands.

    `dispatch(items, (k, of, batch))` dispatches chunk `k` of `of` and
    returns its collect callable; `batch` is None for the first chunk
    and `owner.last_batch_id` as that dispatch left it for the others
    (`owner` is the verifier that numbers batches: the supervisor passes
    the one it wraps), so every span of the batch carries one number. Collecting a chunk
    dispatches the next one not yet dispatched, on the collecting
    thread. A chunk whose dispatch or collect raises is a failed chunk:
    `chunks()` yields None for its verdicts and carries on with the
    rest, and calling the object raises the first such error."""

    def __init__(self, owner, items: Sequence,
                 dispatch: Callable[[Sequence, tuple], Callable]):
        self._owner = owner
        self._items = items
        self._dispatch = dispatch
        self.bounds = chunk_bounds(len(items), MAX_BUCKET)
        self.batch = None
        self._handles = deque()        # dispatched, not yet collected
        self._verdicts: list = []      # per collected chunk: list or None
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self.max_in_flight = 0         # the most ever in flight at once
        for _ in range(MAX_CHUNKS_IN_FLIGHT):
            self._dispatch_next()

    def _dispatch_next(self) -> None:
        k = len(self._verdicts) + len(self._handles)
        if k >= len(self.bounds):
            return
        lo, hi = self.bounds[k]
        try:
            handle = self._dispatch(self._items[lo:hi],
                                    (k, len(self.bounds), self.batch))
        except Exception as e:          # noqa: BLE001 — a failed chunk
            handle = e
        if k == 0:
            self.batch = getattr(self._owner, "last_batch_id", None)
        self._handles.append(handle)
        self.max_in_flight = max(self.max_in_flight, len(self._handles))

    def _collect_next(self) -> None:
        k = len(self._verdicts)
        handle = self._handles.popleft()
        try:
            if isinstance(handle, Exception):
                raise handle
            res = [bool(v) for v in handle()]
        except Exception as e:          # noqa: BLE001 — a failed chunk
            log.warning("chunk %d of %d failed: %r", k, len(self.bounds), e)
            if self._error is None:
                self._error = e
            res = None
        self._verdicts.append(res)
        self._dispatch_next()

    def chunks(self):
        """Yield (lo, hi, verdicts) of each chunk in order, blocking
        until it has landed; verdicts is None for a failed chunk."""
        for k, (lo, hi) in enumerate(self.bounds):
            with self._lock:
                while len(self._verdicts) <= k:
                    self._collect_next()
            yield lo, hi, self._verdicts[k]

    def __call__(self) -> List[bool]:
        out: List[bool] = []
        for _, _, verdicts in self.chunks():
            if verdicts is not None:
                out.extend(verdicts)
        if self._error is not None:
            raise self._error
        return out
