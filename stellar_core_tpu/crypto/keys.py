"""Keys and signature verification — the backend seam.

Reference: src/crypto/SecretKey.{h,cpp}. `PubKeyUtils.verify_sig` is the
single-signature hot path (SecretKey.cpp:427-460) with the global
RandomEvictionCache of 0xffff entries keyed by BLAKE2(key‖sig‖msg)
(SecretKey.cpp:37-60). Signing uses the OpenSSL-backed `cryptography` package
(signatures are standard RFC 8032, byte-identical to libsodium's).

Verification uses the strongest available strict backend:
  1. native C++ (stellar_core_tpu/native) when built — fast path
  2. strict prechecks (canonicality, small-order) + OpenSSL for the equation

Both agree with crypto/ed25519_ref.verify on every input by construction;
tests/test_crypto.py enforces it differentially.
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Optional

try:
    from cryptography.hazmat.primitives.asymmetric import \
        ed25519 as _ossl_ed
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import serialization as _ser
except ImportError:                                  # pragma: no cover
    # gate the OpenSSL backend: containers without the `cryptography`
    # wheel fall back to the pure-python reference implementation
    # (byte-identical RFC 8032 signatures, just slower)
    _ossl_ed = None
    _ser = None
    InvalidSignature = Exception

from . import ed25519_ref
from .sha import blake2b_256
from ..util.cache import RandomEvictionCache

# reference: crypto/SecretKey.cpp:44 — 0xffff entries
VERIFY_CACHE_SIZE = 0xFFFF
_verify_cache: RandomEvictionCache = RandomEvictionCache(VERIFY_CACHE_SIZE)


# per-signature host verifies (every `verify_sig_uncached`): how many,
# and the seconds they took. Plain module attributes beside
# `_verify_cache.hits/.misses`, like them process-wide and like them
# drained by `publish_verify_counts`; no lock, so two threads that
# verify at once (staged apply's workers) can lose an update.
_native_count = 0
_native_seconds = 0.0


def flush_verify_cache_counts() -> tuple:
    """Return (hits, misses) and reset (reference: SecretKey.cpp:324-331)."""
    h, m = _verify_cache.hits, _verify_cache.misses
    _verify_cache.reset_counters()
    return h, m


def publish_verify_counts(metrics, perf) -> None:
    """Drain the process-wide verify counts into a node's two
    registries: the zone `crypto.verify.native` of `perf` (per-signature
    host verifies and their seconds, through `ZoneRegistry.add`) and the
    meters `crypto.verify.cache.hit` / `.miss` of `metrics`. The one
    reader of those counts: every ledger close and the `metrics` admin
    route call it, so a count lands in exactly one node. In a process
    with several nodes that is whichever node drains next, not the node
    that verified. The meters always exist (zero-valued) so scrapers
    see stable families."""
    global _native_count, _native_seconds
    n, sec = _native_count, _native_seconds
    _native_count, _native_seconds = 0, 0.0
    h, m = flush_verify_cache_counts()
    hit = metrics.meter("crypto", "verify", "cache", "hit")
    miss = metrics.meter("crypto", "verify", "cache", "miss")
    if n:
        perf.add("crypto.verify.native", sec, n)
    if h:
        hit.mark(h)
    if m:
        miss.mark(m)


def clear_verify_cache() -> None:
    _verify_cache.clear()


def verify_cache_key(pub: bytes, sig: bytes, msg: bytes) -> bytes:
    """The cache key verify_sig uses (reference: SecretKey.cpp:37-60) —
    exposed so batch front-ends share one derivation."""
    return blake2b_256(pub + sig + msg)


def probe_verify_cache(pub: bytes, sig: bytes,
                       msg: bytes) -> Optional[bool]:
    """Counting cache probe for batch front-ends (the txset
    prevalidator): same key derivation and hit/miss accounting as
    PubKeyUtils.verify_sig's own lookup."""
    return _verify_cache.maybe_get(verify_cache_key(pub, sig, msg))


def seed_verify_cache(pub: bytes, sig: bytes, msg: bytes,
                      ok: bool) -> None:
    """Write a batch-verify result through to the process-wide cache so
    later per-signature verifies of the same tuple (apply-time
    re-verification of flood-admitted or prevalidated txs) hit instead
    of re-verifying."""
    _verify_cache.put(verify_cache_key(pub, sig, msg), bool(ok))


def seed_verify_cache_by_key(key: bytes, ok: bool) -> None:
    """Key-based write-through for callers that already derived the
    key (the verify service derives it once per submit)."""
    _verify_cache.put(key, bool(ok))


def _native_verify() -> Optional[object]:
    """The native C++ strict verifier, if the extension is built."""
    try:
        from ..native import loader
        return loader.get_lib()
    except Exception:
        return None


class PublicKey:
    """32-byte Ed25519 public key (reference: PublicKey XDR union, one arm)."""

    __slots__ = ("raw",)

    def __init__(self, raw: bytes):
        assert len(raw) == 32
        self.raw = bytes(raw)

    def hint(self) -> bytes:
        """Last 4 bytes — the SignatureHint prefilter used before any crypto
        (reference: SignatureUtils::getHint, transactions/SignatureUtils.cpp)."""
        return self.raw[28:]

    def __eq__(self, other) -> bool:
        return isinstance(other, PublicKey) and self.raw == other.raw

    def __hash__(self) -> int:
        return hash(self.raw)

    def __repr__(self) -> str:
        from .strkey import StrKey
        return f"PublicKey({StrKey.encode_ed25519_public(self.raw)})"


class SecretKey:
    """Ed25519 secret key (seed form), reference: crypto/SecretKey.h:22."""

    __slots__ = ("seed", "_ossl", "_pub")

    def __init__(self, seed: bytes):
        assert len(seed) == 32
        self.seed = bytes(seed)
        if _ossl_ed is not None:
            self._ossl = _ossl_ed.Ed25519PrivateKey.from_private_bytes(
                self.seed)
            pub = self._ossl.public_key().public_bytes(
                _ser.Encoding.Raw, _ser.PublicFormat.Raw)
        else:
            self._ossl = None
            lib = _native_verify()
            pub = lib.public_from_seed(self.seed) if lib is not None \
                else ed25519_ref.secret_to_public(self.seed)
        self._pub = PublicKey(pub)

    @classmethod
    def random(cls) -> "SecretKey":
        return cls(os.urandom(32))

    @classmethod
    def from_seed(cls, seed: bytes) -> "SecretKey":
        return cls(seed)

    @classmethod
    def pseudo_random_for_testing(cls, n: int) -> "SecretKey":
        """Deterministic test keys (reference: SecretKey::pseudoRandomForTesting)."""
        return cls(hashlib.sha256(b"test-key-%d" % n).digest())

    def public_key(self) -> PublicKey:
        return self._pub

    def sign(self, msg: bytes) -> bytes:
        if self._ossl is not None:
            return self._ossl.sign(msg)
        # containers without the `cryptography` wheel: the native C
        # signer (byte-identical RFC 8032) — a pure-python pt_mul per
        # signature measured as the TPSMT leg's single largest cost
        # (ISSUE 12: 2.2s of a 6.4s ledger wall went to loadgen + SCP
        # envelope signing)
        lib = _native_verify()
        if lib is not None:
            return lib.sign(self.seed, self._pub.raw, msg)
        return ed25519_ref.sign(self.seed, msg)

    def __repr__(self) -> str:
        return "SecretKey(<hidden>)"


class PubKeyUtils:
    """Static verify helpers (reference: PubKeyUtils, crypto/SecretKey.h:127)."""

    @staticmethod
    def verify_sig(pub: PublicKey | bytes, sig: bytes, msg: bytes,
                   use_cache: bool = True) -> bool:
        raw = pub.raw if isinstance(pub, PublicKey) else pub
        if len(raw) != 32 or len(sig) != 64:
            return False
        if use_cache:
            key = blake2b_256(raw + sig + msg)
            hit = _verify_cache.maybe_get(key)
            if hit is not None:
                return hit
        ok = verify_sig_uncached(raw, sig, msg)
        if use_cache:
            _verify_cache.put(key, ok)
        return ok


def verify_sig_uncached(pub: bytes, sig: bytes, msg: bytes) -> bool:
    """One signature verified on the host, counted and timed (see
    `publish_verify_counts`). `verify_sig`'s miss path, the device
    verifiers' small-batch bypass and the supervisor's native answers
    all come through here."""
    global _native_count, _native_seconds
    t0 = time.perf_counter()
    lib = _native_verify()
    if lib is not None:
        ok = lib.verify(pub, sig, msg)
    else:
        ok = _verify_strict_openssl(pub, sig, msg)
    _native_count += 1
    _native_seconds += time.perf_counter() - t0
    return ok


def _verify_strict_openssl(pub: bytes, sig: bytes, msg: bytes) -> bool:
    """Strict prechecks in Python + OpenSSL for the group equation."""
    if _ossl_ed is None:
        # no OpenSSL backend in this container: the reference
        # implementation is already strict end-to-end
        return ed25519_ref.verify(pub, sig, msg)
    S = int.from_bytes(sig[32:], "little")
    if S >= ed25519_ref.L:
        return False
    A = ed25519_ref.pt_decompress(pub, strict=True)
    if A is None or ed25519_ref.pt_is_small_order(A):
        return False
    R = ed25519_ref.pt_decompress(sig[:32], strict=True)
    if R is None or ed25519_ref.pt_is_small_order(R):
        return False
    try:
        _ossl_ed.Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except InvalidSignature:
        return False
    except Exception:
        # encoding OpenSSL refuses outright — strict path rejects too
        return False
