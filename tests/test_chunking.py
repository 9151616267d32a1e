"""The chunk a large batch is split into (ISSUE 36): `MAX_BUCKET` is
the last rung of the bucket ladder, and everything beyond it runs as
chunks of that one shape, `MAX_CHUNKS_IN_FLIGHT` at a time, each a
dispatch of its own under the supervisor.

The cases run at a largest bucket patched to 16 lanes (a shape the suite
compiles anyway); one test pins the shipped values, so that moving them
is a decision and not a side effect."""

import hashlib
import threading

import pytest

from stellar_core_tpu.crypto.keys import SecretKey, verify_sig_uncached
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.util.metrics import MetricsRegistry

from benchmark.reference import ed25519_oracle

BUCKET = 16
NINE = 8 * BUCKET + 5            # nine chunks, the last a remainder of 5


def _tuples(n: int, bad=()) -> tuple:
    """`n` signed tuples, those at `bad` with a bit of the signature
    flipped, and the oracle's verdict on each."""
    sk = SecretKey.from_seed(hashlib.sha256(b"chunking").digest())
    pub = sk.public_key().raw
    items, want = [], []
    for i in range(n):
        msg = hashlib.sha256(b"chunking-msg-%d" % i).digest()
        sig = sk.sign(msg)
        if i in bad:
            sig = sig[:5] + bytes([sig[5] ^ 4]) + sig[6:]
        items.append((pub, sig, msg))
        want.append(i not in bad)
    assert [ed25519_oracle.verify(*items[i]) for i in bad] == \
        [False] * len(bad)
    return items, want


# bit-flipped tuples on both sides of every boundary of nine chunks
BAD = {k * BUCKET + d for k in range(1, 9) for d in (-1, 0)}


class _StubDevice:
    """What the supervisor wraps: a verifier that numbers batches as
    `TpuBatchVerifier` does and answers with the native verifier's
    verdicts; chunk `slow`'s collect blocks until `release` is set."""

    def __init__(self, slow=None):
        self.last_batch_id = 0
        self.slow = slow
        self.release = threading.Event()
        self.calls = []          # the `chunk` of every dispatch
        self.collected = []      # chunk indexes whose collect returned

    def verify_tuples_async(self, items, chunk=None):
        if chunk is None or chunk[2] is None:
            self.last_batch_id += 1
        self.calls.append(chunk)

        def collect():
            if chunk is not None and chunk[0] == self.slow:
                self.release.wait(60)
            res = [verify_sig_uncached(p, s, m) for p, s, m in items]
            self.collected.append(chunk and chunk[0])
            return res
        return collect


def _device_verifier(metrics):
    """The real pack / enqueue / collect path on the CPU, with the
    shapes its msg32 program is called with written down."""
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    verifier = TpuBatchVerifier(metrics=metrics)
    program, shapes = verifier._jit_msg32, []

    def recording(*args):
        shapes.append(tuple(a.shape for a in args))
        return program(*args)
    verifier._jit_msg32 = recording
    return verifier, program, shapes


def _exactly_the_bucket_is_one_dispatch():
    metrics = MetricsRegistry()
    verifier, _, shapes = _device_verifier(metrics)
    items, want = _tuples(BUCKET, {0, BUCKET - 1})
    handle = verifier.verify_tuples_async(items)
    assert not isinstance(handle, chunking.ChunkedCollect)
    assert not hasattr(handle, "chunks")
    assert list(handle()) == want
    seen = metrics.to_json()
    assert seen["crypto.verify.dispatch.batch"]["count"] == 1
    assert seen["crypto.verify.dispatch.padding"]["sum"] == 0
    assert seen["crypto.verify.dispatch.chunks"]["count"] == 0
    assert len(shapes) == 1 and shapes[0][0] == (BUCKET, 32)
    assert verifier.last_batch_id == 1


def _one_more_is_two_chunks_of_one_shape():
    metrics = MetricsRegistry()
    verifier, program, shapes = _device_verifier(metrics)
    compiled = program._cache_size()
    items, want = _tuples(BUCKET + 1, {BUCKET - 1, BUCKET})
    handle = verifier.verify_tuples_async(items)
    assert isinstance(handle, chunking.ChunkedCollect)
    assert handle.bounds == [(0, BUCKET), (BUCKET, BUCKET + 1)]
    assert handle() == want
    seen = metrics.to_json()
    assert seen["crypto.verify.dispatch.chunks"]["count"] == 2
    assert seen["crypto.verify.dispatch.batch"]["sum"] == BUCKET + 1
    # the remainder of one tuple is padded into the full bucket: both
    # chunks are calls of one shape, so at most one program is compiled
    # for them (none where an earlier test already ran the shape)
    assert seen["crypto.verify.dispatch.padding"]["sum"] == BUCKET - 1
    assert len(shapes) == 2 and len(set(shapes)) == 1
    assert shapes[0] == ((BUCKET, 32),) * 4
    assert program._cache_size() - compiled <= 1
    assert verifier.last_batch_id == 1           # one batch, one number


def _stub_dispatch(fails=(), log=None):
    """A `ChunkedCollect` dispatch that answers True a tuple and keeps
    count of what is dispatched and not yet collected."""
    state = {"out": 0, "most": 0}

    def dispatch(part, chunk):
        state["out"] += 1
        state["most"] = max(state["most"], state["out"])
        if log is not None:
            log.append(("dispatch", chunk[0]))

        def collect():
            state["out"] -= 1
            if log is not None:
                log.append(("collect", chunk[0]))
            if chunk[0] in fails:
                raise RuntimeError(f"chunk {chunk[0]} lost")
            return [i % 3 != 0 for i in part]
        return collect
    return dispatch, state


def _nine_chunks_the_fifth_failing():
    dispatch, _ = _stub_dispatch(fails={4})
    handle = chunking.ChunkedCollect(None, list(range(NINE)), dispatch)
    got = list(handle.chunks())
    assert [(lo, hi) for lo, hi, _ in got] == \
        chunking.chunk_bounds(NINE, BUCKET)
    assert len(got) == 9 and got[-1][:2] == (8 * BUCKET, NINE)
    for k, (lo, hi, verdicts) in enumerate(got):
        if k == 4:
            assert verdicts is None
        else:
            assert verdicts == [i % 3 != 0 for i in range(lo, hi)]
    # the whole-batch call raises the chunk's error, as one bucket's
    # collect would
    with pytest.raises(RuntimeError, match="chunk 4 lost"):
        handle()


def _in_flight_never_passes_the_constant():
    log = []
    dispatch, state = _stub_dispatch(log=log)
    handle = chunking.ChunkedCollect(None, list(range(NINE)), dispatch)
    # what the constructor dispatched before anything was collected
    assert log == [("dispatch", k)
                   for k in range(chunking.MAX_CHUNKS_IN_FLIGHT)]
    assert handle() == [i % 3 != 0 for i in range(NINE)]
    assert state["most"] == handle.max_in_flight == \
        chunking.MAX_CHUNKS_IN_FLIGHT
    assert [k for what, k in log if what == "dispatch"] == list(range(9))
    assert [k for what, k in log if what == "collect"] == list(range(9))
    # collecting chunk k is what dispatches chunk k + in-flight
    at = log.index(("collect", 0))
    assert log[at + 1] == ("dispatch", chunking.MAX_CHUNKS_IN_FLIGHT)


def _supervised_every_chunk_is_a_dispatch():
    from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
    inner = _StubDevice()
    sup = BackendSupervisor(inner, dispatch_deadline_ms=60000.0)
    try:
        items, want = _tuples(NINE, BAD)
        handle = sup.verify_tuples_async(items)
        assert isinstance(handle, chunking.ChunkedCollect)
        assert handle() == want
        assert handle.max_in_flight == chunking.MAX_CHUNKS_IN_FLIGHT
        status = sup.status()
        assert status["dispatches"] == 9 == len(inner.calls)
        assert [c[:2] for c in inner.calls] == [(k, 9) for k in range(9)]
        # the first chunk takes the batch's number, the others carry it
        assert inner.calls[0][2] is None
        assert {c[2] for c in inner.calls[1:]} == {1} == {handle.batch}
        assert inner.last_batch_id == 1
        assert not any(status["failures"].values())
        assert status["state"] == "CLOSED" and not status["quarantined"]
        # a batch that fits the bucket is not split, and is one dispatch
        plain = sup.verify_tuples_async(items[:BUCKET])
        assert not hasattr(plain, "chunks")
        assert plain() == want[:BUCKET]
        assert sup.status()["dispatches"] == 10 and inner.calls[-1] is None
    finally:
        sup.shutdown()


def _supervised_a_chunk_past_its_deadline_falls_back_alone():
    from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
    inner = _StubDevice(slow=3)
    sup = BackendSupervisor(inner, dispatch_deadline_ms=250.0)
    try:
        items, want = _tuples(NINE, BAD)
        got = list(sup.verify_tuples_async(items).chunks())
        # nothing is None: the supervisor answered the late chunk from
        # the native path, in its place
        assert [v for _, _, vs in got for v in vs] == want
        assert sorted(inner.collected) == [0, 1, 2, 4, 5, 6, 7, 8]
        status = sup.status()
        assert status["dispatches"] == 9
        assert status["failures"]["timeout"] == 1
        assert sum(status["failures"].values()) == 1
        assert [q["batch"] for q in status["quarantined"]] == [BUCKET]
        assert status["state"] == "CLOSED"       # one late chunk trips nothing
    finally:
        inner.release.set()
        sup.shutdown()


def _sharded_chunk_is_the_bucket_rounded_up_to_the_mesh():
    """Host half only (nothing runs): on a mesh the chunk's shape is the
    largest bucket rounded up to a multiple of the active devices, the
    same for a full chunk and for a remainder, and rows are shared out
    as `shard_shares` says."""
    import numpy as np
    from stellar_core_tpu.ops.verifier import ShardedBatchVerifier
    verifier = ShardedBatchVerifier(device_min_batch=1)
    assert verifier.ndev >= 4
    items, _ = _tuples(BUCKET)
    pubs = np.frombuffer(b"".join(p for p, _, _ in items), dtype=np.uint8)
    sigs = np.frombuffer(b"".join(s for _, s, _ in items), dtype=np.uint8)
    msgs = [m for _, _, m in items]
    for active, bucket in (((0, 1, 2, 3), BUCKET), ((0, 1, 2), 18)):
        shapes = set()
        for n in (BUCKET, 5):
            packed = verifier._pack(pubs[:n * 32], sigs[:n * 64], msgs[:n],
                                    active=active, full=True)
            assert packed.bucket == bucket and packed.n == n
            assert sum(packed.counts) == n
            assert packed.rows * len(active) == bucket
            shapes.add(tuple(a.shape for a in packed.args))
        assert shapes == {((bucket, 32),) * 4}


CASES = [_exactly_the_bucket_is_one_dispatch,
         _one_more_is_two_chunks_of_one_shape,
         _nine_chunks_the_fifth_failing,
         _in_flight_never_passes_the_constant,
         _supervised_every_chunk_is_a_dispatch,
         _supervised_a_chunk_past_its_deadline_falls_back_alone,
         _sharded_chunk_is_the_bucket_rounded_up_to_the_mesh]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_chunk_size(case, monkeypatch):
    monkeypatch.setattr(chunking, "MAX_BUCKET", BUCKET)
    case()


def test_the_shipped_chunk_size_is_a_decision():
    """4,096 lanes is the rung the ladder's readings pick on a TPU v5e
    (9.8 us a lane; 2,048 is within 5 % of it, 8,192 is 7 % dearer) and
    three in flight keep the device fed where two left it idle between
    40 ms runs (PERF.md sections 6 and 7, PR 36): a change of either is a
    measurement's to make, here and there."""
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.ops.verifier import _bucket_size
    assert chunking.MAX_BUCKET == 4096
    assert chunking.MAX_CHUNKS_IN_FLIGHT == 3
    # the last rung is a rung: a batch of exactly that many tuples pads
    # to the shape its chunks would run
    assert _bucket_size(chunking.MAX_BUCKET) == chunking.MAX_BUCKET
    # constants of the module, not fields of a node's configuration
    assert not [name for name in vars(Config())
                if "BUCKET" in name.upper() and "MAX" in name.upper()
                or "CHUNKS_IN_FLIGHT" in name.upper()]
