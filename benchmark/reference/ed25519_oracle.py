"""The plain reference of signature verification: pure-Python strict
Ed25519 (RFC 8032 arithmetic on Python integers, libsodium-strict
acceptance rules).

A copy, kept here so that no later PR can change the yardstick, of
`stellar_core_tpu/crypto/ed25519_ref.py` as of PR 23 (the program's own
semantic oracle; the original is listed under Open questions in PERF.md
for a later PR to point at this copy or delete). It imports nothing of
the program.
"""
from __future__ import annotations

import hashlib
from typing import Optional, Tuple

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1) mod p

# Extended homogeneous coordinates (X, Y, Z, T) with x=X/Z, y=Y/Z, xy=T/Z.
Point = Tuple[int, int, int, int]
IDENTITY: Point = (0, 1, 1, 0)

# base point: y = 4/5
_by = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int, sign: int) -> Optional[int]:
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if x == 0 and sign == 1:
        return None  # "-0" is not a valid encoding
    if x & 1 != sign:
        x = P - x
    return x


_bx = _recover_x(_by, 0)
assert _bx is not None
BASE: Point = (_bx, _by, 1, _bx * _by % P)


def pt_add(p: Point, q: Point) -> Point:
    # add-2008-hwcd-3 (same formulas the ref10/libsodium family uses)
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = (Y1 - X1) * (Y2 - X2) % P
    B = (Y1 + X1) * (Y2 + X2) % P
    C = 2 * T1 * D % P * T2 % P
    Dd = 2 * Z1 * Z2 % P
    E = B - A
    F = Dd - C
    G = Dd + C
    H = B + A
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def pt_double(p: Point) -> Point:
    return pt_add(p, p)


def pt_mul(s: int, p: Point) -> Point:
    q = IDENTITY
    while s > 0:
        if s & 1:
            q = pt_add(q, p)
        p = pt_double(p)
        s >>= 1
    return q


def pt_equal(p: Point, q: Point) -> bool:
    X1, Y1, Z1, _ = p
    X2, Y2, Z2, _ = q
    return (X1 * Z2 - X2 * Z1) % P == 0 and (Y1 * Z2 - Y2 * Z1) % P == 0


def pt_neg(p: Point) -> Point:
    X, Y, Z, T = p
    return (P - X if X else 0, Y, Z, P - T if T else 0)


def pt_is_small_order(p: Point) -> bool:
    """Order divides 8 <=> [8]P = identity (libsodium has_small_order)."""
    return pt_equal(pt_mul(8, p), IDENTITY)


def pt_compress(p: Point) -> bytes:
    X, Y, Z, _ = p
    zi = pow(Z, P - 2, P)
    x = X * zi % P
    y = Y * zi % P
    return int.to_bytes(y | ((x & 1) << 255), 32, "little")


def pt_decompress(s: bytes, strict: bool = True) -> Optional[Point]:
    if len(s) != 32:
        return None
    val = int.from_bytes(s, "little")
    y = val & ((1 << 255) - 1)
    sign = val >> 255
    if strict and y >= P:
        return None
    y %= P
    x = _recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def _clamp(h32: bytes) -> int:
    a = bytearray(h32)
    a[0] &= 248
    a[31] &= 127
    a[31] |= 64
    return int.from_bytes(bytes(a), "little")


def secret_to_public(seed: bytes) -> bytes:
    a = _clamp(hashlib.sha512(seed).digest()[:32])
    return pt_compress(pt_mul(a, BASE))


def sign(seed: bytes, msg: bytes) -> bytes:
    h = hashlib.sha512(seed).digest()
    a = _clamp(h[:32])
    prefix = h[32:]
    A_enc = pt_compress(pt_mul(a, BASE))
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    R = pt_mul(r, BASE)
    R_enc = pt_compress(R)
    k = int.from_bytes(hashlib.sha512(R_enc + A_enc + msg).digest(), "little") % L
    S = (r + k * a) % L
    return R_enc + int.to_bytes(S, 32, "little")


def compute_k(R_enc: bytes, A_enc: bytes, msg: bytes) -> int:
    """k = SHA512(R‖A‖M) mod L — the host-side hash step of batch verify."""
    return int.from_bytes(hashlib.sha512(R_enc + A_enc + msg).digest(), "little") % L


def verify(pub: bytes, sig: bytes, msg: bytes) -> bool:
    """Strict verification — the framework-wide accept/reject contract."""
    if len(pub) != 32 or len(sig) != 64:
        return False
    S = int.from_bytes(sig[32:], "little")
    if S >= L:
        return False
    A = pt_decompress(pub, strict=True)
    if A is None:
        return False
    R = pt_decompress(sig[:32], strict=True)
    if R is None:
        return False
    if pt_is_small_order(A) or pt_is_small_order(R):
        return False
    k = compute_k(sig[:32], pub, msg)
    # [S]B == R + [k]A
    return pt_equal(pt_mul(S, BASE), pt_add(R, pt_mul(k, A)))
