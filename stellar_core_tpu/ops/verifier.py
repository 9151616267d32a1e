"""Batch Ed25519 verifier: host prep + TPU kernel + sharding.

This is the TPU implementation of the crypto-verifier seam (reference:
PubKeyUtils::verifySig, crypto/SecretKey.cpp:427-460; batch collection
points: txset validation herder/TxSetUtils.cpp:200 and catchup replay
catchup/ApplyCheckpointWork.h — see SURVEY.md §3.2/§3.3).

Pipeline per batch of (pubkey, sig, msg):
  1. host (native C++):
     k = SHA512(R‖A‖M) mod L; S<L check; strict decompress + small-order
     checks on A and R; affine -A coords.  (SHA-512's 64-bit rotates are
     hostile to TPU int ops — SURVEY §7 "hard parts" — so hashing stays
     host-side; only the scalar muls go on device.)
  2. pad to a power-of-two bucket (static shapes => one XLA program per
     bucket size, no recompiles).
  3. device: Shamir double-scalar-mult + compress + compare (ed25519_kernel).
  4. AND host flags, unpad.

Accept/reject is bit-identical to the oracle (ed25519_ref.verify) and is
enforced differentially in tests/test_tpu_verifier.py.

Multi-chip: `make_sharded_verify` shard_maps the kernel over a 1-D 'dp'
mesh axis — signatures are embarrassingly data-parallel (SURVEY §5.7),
so the only cross-device traffic is the result gather.

Mesh health (PR 13): `ShardedBatchVerifier` dispatches padded
PER-SHARD buckets over the mesh of *active* devices — the SNIPPETS §2–3
mesh-dispatch shape: a shard_map-wrapped jit per active set, with a
single-device short-circuit (plain jit pinned by `device_put`) when
only one device survives. `set_active_devices` shrinks/regrows the
mesh live (the per-device circuit breakers in
ops/backend_supervisor.py drive it), including non-power-of-two
surviving meshes — the global bucket stays a multiple of the ACTIVE
device count, doubling from the smallest such multiple ≥ MIN_BUCKET.
Per-device dispatch accounting (`crypto.verify.dispatch.device<N>.*`)
gives the breaker the signals to judge a sick chip against its
siblings. Results are byte-identical across mesh shapes: every lane
runs the identical per-lane kernel; only the shard layout moves.
"""

from __future__ import annotations

import time as _time
from types import SimpleNamespace
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PSpec
from jax import shard_map

from . import chunking, ed25519_kernel
from .shard_math import shard_shares
from ..util import chaos, tracing

MIN_BUCKET = 8

# Below this many signatures verify_tuples_async skips the device: the
# fixed cost of a dispatch (packing, transfer, launch, result sync) is
# paid per batch, so tiny batches go to the native per-signature
# verifier instead. The crossover is not measured on the chip. Both
# paths are the same strict verify. The module default of 1 means
# "never bypass", so the kernel tests exercise the device path down to
# a batch of one; the node passes its VERIFY_DEVICE_MIN_BATCH config
# field as the constructor's `device_min_batch`.
DEVICE_MIN_BATCH = 1


def _bucket_size(n: int, minimum: int = MIN_BUCKET) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


# (program, lanes) of every shape this process has run. A jit keeps one
# executable a shape for its life, whichever verifier made the call, so
# a second node of a process finds here what the first one loaded.
_SHAPES_RUN: set = set()


def _first_run(d) -> bool:
    """Whether the packed batch `d` is this process's first of its
    program and lanes; it is on record from here on."""
    key = (d.fn, d.bucket)
    if key in _SHAPES_RUN:
        return False
    _SHAPES_RUN.add(key)
    return True


def _native():
    """The native library; one that cannot be built is an error (the
    Python-oracle prep it used to fall back to is ~1000x slower)."""
    from ..native import loader
    return loader.get_lib()


def host_prepare(pubs: np.ndarray, sigs: np.ndarray, msgs: Sequence[bytes]):
    """Returns (k (n,32) u8, neg_a (n,64) u8, ok (n,) bool)."""
    lib = _native()
    offsets = np.zeros(len(msgs) + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    blob = b"".join(msgs)
    k, s_ok = lib.batch_prepare(pubs, sigs, blob, offsets)
    neg_a, pt_ok = lib.batch_host_precheck(pubs, sigs)
    return k, neg_a, s_ok & pt_ok


def host_k(pubs: np.ndarray, sigs: np.ndarray, msgs: Sequence[bytes]):
    """v2 host prep: just k = SHA512(R‖A‖M) mod L, (n,32) u8 — point
    decompression and all canonicality checks run on device
    (ed25519_kernel.verify_kernel_full). SHA-512 stays host-side: 64-bit
    rotates are hostile to the TPU int units (SURVEY.md §7 hard parts)."""
    lib = _native()
    offsets = np.zeros(len(msgs) + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    k, _ = lib.batch_prepare(pubs, sigs, b"".join(msgs), offsets)
    return k


def _pad_u8(arr: np.ndarray, bucket: int) -> np.ndarray:
    """(n,32) u8 -> (bucket,32) u8, zero-padded (pad lanes decode as the
    torsion point y=0 and are rejected on device; results are sliced off)."""
    n = arr.shape[0]
    if n == bucket:
        return np.ascontiguousarray(arr)
    out = np.zeros((bucket, 32), dtype=np.uint8)
    out[:n] = arr
    return out


class TpuBatchVerifier:
    """Batch verifier on the default JAX backend (TPU in production,
    CPU mesh in tests). Thread-compatible with the sync seam: results are
    per-signature bools identical to PubKeyUtils.verify_sig.

    v2 pipeline: uint8 transfer (128 B/sig over the host link), SHA-512 on
    host, everything else — decompression, strict checks, double scalar
    mult, compare — on device."""

    _shared_jit = None   # one compiled program per process, not per instance
    _shared_jit_msg32 = None

    @classmethod
    def _ensure_shared_jits(cls):
        if TpuBatchVerifier._shared_jit is None:
            TpuBatchVerifier._shared_jit = jax.jit(
                ed25519_kernel.verify_kernel_full)
            TpuBatchVerifier._shared_jit_msg32 = jax.jit(
                ed25519_kernel.verify_kernel_msg32)

    def __init__(self, perf=None, device_min_batch=DEVICE_MIN_BATCH,
                 metrics=None):
        self._ensure_shared_jits()
        self._jit = TpuBatchVerifier._shared_jit
        self._jit_msg32 = TpuBatchVerifier._shared_jit_msg32
        self._min_bucket = MIN_BUCKET
        self._device_min_batch = int(device_min_batch)
        self.perf = perf  # per-app zone registry (None = process default)
        self._init_dispatch_metrics(metrics)

    # ---------------------------------------------------- loaded shapes --
    _loaded: Tuple[int, ...] = ()   # lanes of the shapes loaded, ascending

    def load_shapes(self, buckets) -> List[int]:
        """Make the tx-hash program ready at each of `buckets` (lanes,
        each rounded up to a bucket) before any batch needs it: one run
        on zeros where this process has not run the shape yet (its
        trace, lowering and compile, or the read of the compile cache),
        nothing where it has. From then on a batch that one of them
        holds is padded to the smallest that does (`_bucket_for`), so
        no batch below the largest meets a shape of its own. Returns
        the shapes loaded, ascending. A node calls it once, when it
        starts (`Application.start`); no dispatch is counted."""
        for lanes in sorted({_bucket_size(int(b), self._min_bucket)
                             for b in buckets}):
            zeros = np.zeros((lanes, 64), dtype=np.uint8)
            d = self._pack(zeros[:, :32], zeros, [bytes(32)] * lanes)
            if _first_run(d):
                np.asarray(d.fn(*d.args))
            self._loaded = tuple(sorted({lanes, *self._loaded}))
        if self._m_shape_loaded is not None:
            self._m_shape_loaded.set_count(len(self._loaded))
        return self.loaded_shapes

    @property
    def loaded_shapes(self) -> List[int]:
        return list(self._loaded)

    def _bucket_for(self, n: int, minimum: int) -> int:
        """Lanes of the program a batch of `n` runs on: the smallest
        loaded shape that holds it (and divides by the devices that
        share it, of which `minimum` is a multiple), else its own
        power-of-two bucket."""
        for b in self._loaded:
            if b >= n and b % minimum == 0:
                return b
        return _bucket_size(n, minimum)

    def _launch(self, d: SimpleNamespace):
        """The jit call of a packed batch. A shape this process has not
        run yet traces and compiles inside it, on the caller's thread:
        counted (`crypto.verify.shape.missed`)."""
        if _first_run(d) and self._m_shape_missed is not None:
            self._m_shape_missed.inc()
        return d.fn(*d.args)

    def device_info(self) -> dict:
        """{platform, kind, count} of the devices THIS verifier
        dispatches to — the `device` of `backendstatus`, so a process
        that must stay off JAX can tell which device verified."""
        devs = getattr(self, "devices", None) or jax.devices()[:1]
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def set_device_min_batch(self, n: int) -> None:
        """Live re-tune of the host-bypass cutoff (ops/controller.py;
        inherited by the sharded/hybrid verifiers, proxied through the
        backend supervisor). A plain attribute swap read once per
        flush — no torn state possible."""
        self._device_min_batch = max(1, int(n))

    def _init_dispatch_metrics(self, metrics) -> None:
        """Per-dispatch device accounting (telemetry time-series /
        ROADMAP item 1 groundwork): batch size, padding waste (lanes
        burnt on the power-of-two bucket), and dispatch→collect wall
        time — the per-device health signals a per-device breaker will
        consume. None = accounting off (constructors outside a node)."""
        # running number of the batches given to verify_tuples_async:
        # the `batch` arg of every span in the life of one batch (pack,
        # enqueue, collect here; adoption in catchup), on whichever
        # thread it runs. Read it right after the dispatch returns.
        self.last_batch_id = 0
        if metrics is None:
            self._m_batch = self._m_padding = self._m_wall = None
            self._m_host = self._m_chunks = None
            self._m_shape_loaded = self._m_shape_missed = None
            return
        self._m_batch = metrics.new_histogram(
            "crypto.verify.dispatch.batch")
        self._m_padding = metrics.new_histogram(
            "crypto.verify.dispatch.padding")
        self._m_wall = metrics.new_timer("crypto.verify.dispatch.wall")
        # entry of verify_tuples_async to the return of the enqueue:
        # what a dispatch costs the thread that makes it
        self._m_host = metrics.new_timer("crypto.verify.dispatch.host")
        # chunks dispatched of batches larger than the largest bucket
        # (ops/chunking.py); a batch that fits one bucket adds nothing
        self._m_chunks = metrics.new_counter(
            "crypto.verify.dispatch.chunks")
        # shapes `load_shapes` made ready, and dispatches that met a
        # shape no one had (expected 0 on a node that loaded its own)
        self._m_shape_loaded = metrics.new_counter(
            "crypto.verify.shape.loaded")
        self._m_shape_missed = metrics.new_counter(
            "crypto.verify.shape.missed")

    def verify_batch(self, pubs: np.ndarray, sigs: np.ndarray,
                     msgs: Sequence[bytes]) -> np.ndarray:
        return self.verify_batch_async(pubs, sigs, msgs)()

    def verify_batch_async(self, pubs: np.ndarray, sigs: np.ndarray,
                           msgs: Sequence[bytes], _active=None):
        """Dispatch a batch without blocking; returns a zero-arg callable
        that yields the (n,) bool results. Callers with several batches in
        flight (catchup prevalidation) overlap host
        SHA-512 + transfer of batch i+1 with device compute of batch i.
        `_active` pins an explicit device set on a mesh verifier (the
        per-device canary probe path); None uses the live mesh."""
        if len(msgs) == 0:
            return lambda: np.zeros(0, dtype=bool)
        return self._enqueue(self._pack(pubs, sigs, msgs, _active))

    def _pack(self, pubs: np.ndarray, sigs: np.ndarray,
              msgs: Sequence[bytes], active=None,
              full: bool = False) -> SimpleNamespace:
        """Host half of a dispatch: the padded arrays of the batch's
        bucket and the program that takes them. `full` pads to the
        largest bucket whatever `n` is: a chunk of a split batch runs
        the one shape its siblings run."""
        n = len(msgs)
        pubs = np.asarray(pubs, dtype=np.uint8).reshape(n, 32)
        sigs = np.asarray(sigs, dtype=np.uint8).reshape(n, 64)
        bucket = chunking.MAX_BUCKET if full \
            else self._bucket_for(n, self._min_bucket)
        if all(len(m) == 32 for m in msgs):
            # tx-hash hot path: ship M raw, SHA-512 + mod L on device —
            # no per-signature host work
            fn = self._jit_msg32
            last = np.frombuffer(b"".join(msgs),
                                 dtype=np.uint8).reshape(n, 32)
        else:
            fn = self._jit
            last = host_k(pubs, sigs, msgs)
        args = (_pad_u8(pubs, bucket),
                _pad_u8(sigs[:, :32], bucket),
                _pad_u8(np.ascontiguousarray(sigs[:, 32:]), bucket),
                _pad_u8(last, bucket))
        return SimpleNamespace(fn=fn, args=args, n=n, bucket=bucket)

    def _enqueue(self, d: SimpleNamespace):
        """Device half: the jit call (transfer and launch; on a shape's
        first call also its trace, lowering and compile) and the
        collect callable."""
        out = self._launch(d)
        n = d.n
        if self._m_batch is None:
            return lambda: np.asarray(out)[:n]
        # dispatch accounting: occupancy and padding recorded at
        # dispatch, wall time at FIRST collect (the async split —
        # collect blocks on device completion, so first-collect wall
        # is the true dispatch→results latency)
        self._m_batch.update(n)
        self._m_padding.update(d.bucket - n)
        t0 = _time.perf_counter()
        state = {"done": False}

        def collect():
            res = np.asarray(out)[:n]
            if not state["done"]:
                state["done"] = True
                self._m_wall.update(_time.perf_counter() - t0)
            return res
        return collect

    def verify_tuples(
            self, items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
        return self.verify_tuples_async(items)()

    def verify_tuples_async(
            self, items: Sequence[Tuple[bytes, bytes, bytes]],
            chunk: Optional[tuple] = None):
        """Non-blocking verify_tuples: dispatches host prep + transfer +
        device compute and returns a zero-arg callable yielding the
        List[bool]. Used to overlap checkpoint N+1's signature batch with
        checkpoint N's sequential apply in catchup. The crypto.batchVerify
        perf zone wraps dispatch and (separately) collection, so the
        accounting survives the async split.

        A batch larger than the largest bucket runs as chunks of it
        (ops/chunking.py): the callable returned is then a
        `ChunkedCollect`, whose `chunks()` also hands out each chunk's
        verdicts as it lands. `chunk` = (k, of, batch) marks a call as
        chunk `k` of `of` of the split batch numbered `batch` (None:
        number it here); whoever splits the batch passes it."""
        if not items:
            return lambda: []
        if chunk is None and len(items) > chunking.MAX_BUCKET:
            return chunking.ChunkedCollect(self, items,
                                           self.verify_tuples_async)
        if chaos.ENABLED:
            # device-verifier fault seam: an injected io_error raises
            # BEFORE any dispatch — callers must fall back to the
            # native per-signature path (semantics are identical).
            # Fired before the small-batch bypass decision so the seam
            # contract is batch-size independent.
            chaos.point("ops.verifier.batch", n=len(items))
        t_in = _time.perf_counter()
        from ..util.perf import default_registry
        registry = self.perf or default_registry
        if chunk is None and len(items) < self._device_min_batch:
            # small-batch CPU bypass: the fixed device dispatch cost
            # loses to the native verifier below the cutoff, so tiny
            # flushes (the verify service's deadline stragglers) stay
            # on host — same strict accept/reject either way (the
            # remainder of a split batch is no such flush)
            from ..crypto.keys import verify_sig_uncached
            targs = {"n": len(items)} if tracing.ENABLED else None
            with registry.zone("crypto.batchVerify.native", targs=targs):
                res = [verify_sig_uncached(p, s, m) for p, s, m in items]
            return lambda: res
        if chunk is None or chunk[2] is None:
            self.last_batch_id = batch = self.last_batch_id + 1
        else:
            batch = chunk[2]
        # one dict for every span of this batch (of this chunk of it),
        # on both threads; `bucket` is known once it is packed
        targs = {"batch": batch, "n": len(items)} \
            if tracing.ENABLED else None
        if chunk is not None:
            if targs is not None:
                targs["chunk"], targs["of"] = chunk[0], chunk[1]
            if self._m_chunks is not None:
                self._m_chunks.inc()
        with registry.zone("crypto.batchVerify", targs=targs):
            with registry.zone("crypto.batchVerify.pack", targs=targs):
                pubs = np.frombuffer(b"".join(p for p, _, _ in items),
                                     dtype=np.uint8).reshape(-1, 32)
                sigs = np.frombuffer(b"".join(s for _, s, _ in items),
                                     dtype=np.uint8).reshape(-1, 64)
                packed = self._pack(pubs, sigs, [m for _, _, m in items],
                                    full=chunk is not None)
                if targs is not None:
                    targs["bucket"] = packed.bucket
            with registry.zone("crypto.batchVerify.enqueue", targs=targs):
                handle = self._enqueue(packed)
        if self._m_host is not None:
            self._m_host.update(_time.perf_counter() - t_in)

        def collect():
            with registry.zone("crypto.batchVerify", targs=targs), \
                    registry.zone("crypto.batchVerify.collect",
                                  targs=targs):
                return list(handle())
        return collect

    def verify_tuples_async_on(self, device_index: int, items):
        """Pinned single-device dispatch — the per-device canary-probe
        entry point (ops/backend_supervisor.py). The single-device
        verifier has exactly one device, so this is the plain path;
        the sharded verifier overrides it with real placement."""
        if int(device_index) != 0:
            raise IndexError(
                f"single-device verifier has no device {device_index}")
        return self.verify_tuples_async(items)


def make_sharded_verify(mesh: Mesh, axis: str = "dp",
                        kernel=ed25519_kernel.verify_kernel_full):
    """shard_map'd v2/v3 kernel over a 1-D mesh axis: the batch axis of
    the (B,32) uint8 inputs is sharded, each device runs the identical
    decompress+scalar-mult program on its shard; the only cross-device
    traffic is the (B,) bool result gather. B must divide by mesh size."""
    spec = PSpec(axis, None)
    f = shard_map(kernel, mesh=mesh,
                  in_specs=(spec,) * 4, out_specs=PSpec(axis))
    return jax.jit(f)


class ShardedBatchVerifier(TpuBatchVerifier):
    """Data-parallel verifier over the ACTIVE subset of a 1-D device
    mesh.

    Each dispatch splits the batch into padded per-shard buckets —
    shard ``s`` owns rows ``[s*rows, s*rows+count_s)`` of the global
    array, the rest of its slice is zero padding (rejected on device
    like every pad lane) — and runs the SNIPPETS §2–3 mesh-dispatch
    pattern over the active devices: a ``shard_map``-wrapped jit when
    two or more survive, a plain jit pinned via ``device_put`` when
    exactly one does (the single-device short-circuit). Programs are
    cached per (active set, kernel), so 8→7→8 health transitions reuse
    compiled meshes. Non-power-of-two surviving meshes work because
    the global bucket doubles from the smallest multiple of the ACTIVE
    count ≥ MIN_BUCKET, never from a power of two."""

    def __init__(self, devices: Optional[list] = None, axis: str = "dp",
                 perf=None, device_min_batch=DEVICE_MIN_BATCH, metrics=None):
        self.perf = perf
        self._device_min_batch = int(device_min_batch)
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        self.ndev = len(self.devices)
        self._axis = axis
        self.mesh = Mesh(np.array(self.devices), (axis,))
        self._active: Tuple[int, ...] = tuple(range(self.ndev))
        # (active tuple, msg32) -> (compiled fn, pin device or None);
        # built lazily so a mesh shape is only compiled when
        # dispatched, LRU-bounded so independently flapping breakers
        # (up to 2^ndev distinct survivor subsets, each an XLA
        # executable) cannot grow the hot path's memory forever — the
        # shapes a live mesh actually revisits (full set, full-minus-
        # one, the current survivors) stay resident
        from collections import OrderedDict
        import threading
        self._programs: "OrderedDict" = OrderedDict()
        self._max_programs = 16
        # guards the cache bookkeeping only (never held across a
        # compile): probe timers and dispatch callers reach _program
        # concurrently, and a get/move_to_end racing an eviction
        # would KeyError on the hot path
        self._programs_lock = threading.Lock()
        # bucket sizes must stay divisible by the mesh size: start from the
        # smallest multiple of ndev >= MIN_BUCKET (doubling in _bucket_size
        # preserves divisibility)
        self._min_bucket = self._min_bucket_for(self.ndev)
        self._init_dispatch_metrics(metrics)

    # ------------------------------------------------------ mesh health --
    @staticmethod
    def _min_bucket_for(nact: int) -> int:
        return ((MIN_BUCKET + nact - 1) // nact) * nact

    def set_active_devices(self, indices) -> None:
        """Live mesh shrink/regrow (driven by the per-device breakers
        in ops/backend_supervisor.py): from the next dispatch on, the
        batch shards over exactly `indices` (global positions in
        ``self.devices``); an excluded device receives ZERO dispatches.
        A plain tuple swap — a concurrent dispatch sees the old or the
        new mesh, never a torn one."""
        idx = tuple(sorted({int(i) for i in indices}))
        if not idx:
            raise ValueError("active device set must not be empty "
                             "(mesh-empty falls back to native in the "
                             "backend supervisor)")
        if idx[0] < 0 or idx[-1] >= self.ndev:
            raise IndexError(f"device index out of range: {idx}")
        self._active = idx

    def active_indices(self) -> Tuple[int, ...]:
        return self._active

    def _program(self, active: Tuple[int, ...], msg32: bool):
        """(compiled fn, pin) for one active set: shard_map over the
        surviving mesh, or the shared single-device jit + an explicit
        pin device for the short-circuit."""
        key = (active, bool(msg32))
        with self._programs_lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                return prog
        # build OUTSIDE the lock: a concurrent duplicate build of the
        # same key is wasteful but harmless (last insert wins)
        prog = self._compile(active, msg32)
        with self._programs_lock:
            self._programs[key] = prog
            while len(self._programs) > self._max_programs:
                self._programs.popitem(last=False)
        return prog

    def _compile(self, active: Tuple[int, ...], msg32: bool):
        """Build one (compiled fn, pin device or None) for an active
        set — the only step subclasses override (the hybrid verifier's
        full-mesh 2-D program); the LRU protocol above stays in one
        place."""
        kernel = (ed25519_kernel.verify_kernel_msg32 if msg32
                  else ed25519_kernel.verify_kernel_full)
        if len(active) == 1:
            self._ensure_shared_jits()
            fn = (TpuBatchVerifier._shared_jit_msg32 if msg32
                  else TpuBatchVerifier._shared_jit)
            return (fn, self.devices[active[0]])
        mesh = Mesh(np.array([self.devices[i] for i in active]),
                    (self._axis,))
        return (make_sharded_verify(mesh, self._axis, kernel), None)

    # --------------------------------------------------------- metrics --
    def _init_dispatch_metrics(self, metrics) -> None:
        super()._init_dispatch_metrics(metrics)
        if metrics is None:
            self._m_dev = None
            return
        # per-device accounting (crypto.verify.dispatch.device<N>.*):
        # the per-device breaker judges a sick chip against its
        # siblings from these — batch share, padding burnt, and the
        # dispatch→collect wall the shard rode (for a collective
        # launch the wall is shared; the discriminating signals are
        # the per-device dispatch/skip/failure counters upstairs)
        self._m_dev = [
            {"batch": metrics.new_histogram(
                "crypto.verify.dispatch.device%d.batch" % i),
             "padding": metrics.new_histogram(
                 "crypto.verify.dispatch.device%d.padding" % i),
             "wall": metrics.new_timer(
                 "crypto.verify.dispatch.device%d.wall" % i)}
            for i in range(self.ndev)]

    # -------------------------------------------------------- dispatch --
    def _pack(self, pubs: np.ndarray, sigs: np.ndarray,
              msgs: Sequence[bytes], active=None,
              full: bool = False) -> SimpleNamespace:
        """Mesh dispatch, host half: padded per-shard buckets over the
        active devices (`active` pins an explicit set, None uses the
        live mesh) and the program of that set. `full`: the largest
        bucket (a chunk of a split batch), rounded up to a multiple of
        the active devices."""
        n = len(msgs)
        active = tuple(active) if active is not None else self._active
        nact = len(active)
        pubs = np.asarray(pubs, dtype=np.uint8).reshape(n, 32)
        sigs = np.asarray(sigs, dtype=np.uint8).reshape(n, 64)
        bucket = -(-chunking.MAX_BUCKET // nact) * nact if full \
            else self._bucket_for(n, self._min_bucket_for(nact))
        rows = bucket // nact
        counts = shard_shares(n, nact)

        def layout(arr: np.ndarray) -> np.ndarray:
            # per-shard padded buckets: shard s gets its rows at the
            # head of its slice, zero padding behind (pad lanes decode
            # as the torsion point y=0 and are rejected on device)
            out = np.zeros((bucket, arr.shape[1]), dtype=np.uint8)
            off = 0
            for s, c in enumerate(counts):
                if c:
                    out[s * rows:s * rows + c] = arr[off:off + c]
                off += c
            return out

        msg32 = all(len(m) == 32 for m in msgs)
        if msg32:
            # tx-hash hot path: SHA-512 + mod L on device (see
            # TpuBatchVerifier._pack)
            last = np.frombuffer(b"".join(msgs),
                                 dtype=np.uint8).reshape(n, 32)
        else:
            last = host_k(pubs, sigs, msgs)
        args = (layout(pubs), layout(sigs[:, :32]),
                layout(np.ascontiguousarray(sigs[:, 32:])), layout(last))
        fn, pin = self._program(active, msg32)
        return SimpleNamespace(fn=fn, args=args, n=n, bucket=bucket,
                               pin=pin, active=active, rows=rows,
                               counts=counts)

    def _enqueue(self, d: SimpleNamespace):
        args, active, rows, counts = d.args, d.active, d.rows, d.counts
        nact = len(active)
        if d.pin is not None:
            args = d.args = tuple(jax.device_put(a, d.pin) for a in args)
        out = self._launch(d)

        def unshard(res: np.ndarray) -> np.ndarray:
            parts = [res[s * rows:s * rows + counts[s]]
                     for s in range(nact)]
            return parts[0] if nact == 1 else np.concatenate(parts)

        if self._m_batch is None:
            return lambda: unshard(np.asarray(out))
        self._m_batch.update(d.n)
        self._m_padding.update(d.bucket - d.n)
        for s, c in enumerate(counts):
            dm = self._m_dev[active[s]]
            dm["batch"].update(c)
            dm["padding"].update(rows - c)
        t0 = _time.perf_counter()
        state = {"done": False}

        def collect():
            res = np.asarray(out)
            if not state["done"]:
                state["done"] = True
                dt = _time.perf_counter() - t0
                self._m_wall.update(dt)
                for s in range(nact):
                    self._m_dev[active[s]]["wall"].update(dt)
            return unshard(res)
        return collect

    def verify_tuples_async_on(self, device_index: int, items):
        """Dispatch one batch pinned to a SINGLE device, bypassing the
        active mesh — the per-device canary-probe path: probing a sick
        chip must not ride (or disturb) the survivors' mesh. Same
        min-batch bypass and accept/reject as verify_tuples_async."""
        device_index = int(device_index)
        if not 0 <= device_index < self.ndev:
            raise IndexError(f"no device {device_index} in this mesh")
        n = len(items)
        if n == 0:
            return lambda: []
        if chaos.ENABLED:
            # same seam contract as verify_tuples_async: the probe is
            # a device dispatch like any other
            chaos.point("ops.verifier.batch", n=n)
        if n < self._device_min_batch:
            from ..crypto.keys import verify_sig_uncached
            res = [verify_sig_uncached(p, s, m) for p, s, m in items]
            return lambda: res
        pubs = np.frombuffer(b"".join(p for p, _, _ in items),
                             dtype=np.uint8).reshape(n, 32)
        sigs = np.frombuffer(b"".join(s for _, s, _ in items),
                             dtype=np.uint8).reshape(n, 64)
        handle = self.verify_batch_async(pubs, sigs,
                                         [m for _, _, m in items],
                                         _active=(device_index,))
        return lambda: list(handle())
