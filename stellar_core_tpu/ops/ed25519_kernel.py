"""Batch Ed25519 verification kernel for TPU (JAX/XLA).

Computes, for a batch of prepared signatures, whether
    compress([S]B + [k](-A)) == R_bytes
which (given the host-side strict prechecks) is exactly libsodium's
cofactorless check [S]B == R + [k]A. Semantics oracle:
stellar_core_tpu/crypto/ed25519_ref.py; reference hot path:
crypto/SecretKey.cpp:427-460, batch collection points described in
SURVEY.md §3.2/§3.3.

Device-side design:
- Points in extended twisted-Edwards coordinates (X,Y,Z,T); the unified
  add-2008-hwcd-3 law is *complete* on edwards25519 (a=-1 square, d
  non-square), so the whole scalar ladder is branch-free — ideal for XLA:
  no data-dependent control flow, static shapes, one fused scan.
- Windowed Shamir/Straus interleaving (w=2): one shared doubling chain,
  127 iterations of two doublings plus one addition selected from the
  16-entry table [i]B + [j](-A) by arithmetic one-hot (no gather, no
  branches).
- Batch is the lane axis (see fe8.py); scan carries 4 field elements.

Host-side prep (native C++ or Python fallback, see verifier.py) supplies:
  S bytes, k = SHA512(R‖A‖M) mod L bytes, affine -A, R bytes, and the
  strict canonicality/small-order accept flags.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import fe8
from ..crypto import ed25519_ref as _ref

def _base_multiple_consts(m: int):
    """Affine limbs of [m]B computed in the python oracle (host-side,
    once at import; y = 4/5, x recovered with even sign)."""
    x, y, z, _ = _ref.pt_mul(m, _ref.BASE)
    zi = pow(z, _ref.P - 2, _ref.P)
    ax, ay = x * zi % _ref.P, y * zi % _ref.P
    return (fe8.const(ax), fe8.const(ay), fe8.ONE,
            fe8.const(ax * ay % _ref.P))


# [1]B, [2]B, [3]B — constants for the windowed Shamir table
_BASE_MULTS = [None] + [_base_multiple_consts(m) for m in (1, 2, 3)]
BASE_X, BASE_Y, _, BASE_T = _BASE_MULTS[1]

# identity (0, 1, 1, 0)
IDENT = (fe8.ZERO, fe8.ONE, fe8.ONE, fe8.ZERO)


# 2d mod p — cached-format table component (ref10 ge_cached T2d analogue)
D2 = fe8.const((2 * ((-121665 * pow(121666, _ref.P - 2, _ref.P)) % _ref.P))
               % _ref.P)


# Lane-concatenated "wide" muls measured slower than plain narrow muls on
# v5e (concat copies outweigh any latency win), so the stacked path is off;
# kept switchable for future hardware.
WIDE_MULS = False

# ladder scan unrolling (XLA scheduling freedom across iterations):
# one step an iteration; other factors are not measured on the chip
SCAN_UNROLL = 1


def _mulw(xs, ys):
    """len(xs) independent field muls, optionally packed into one wide op."""
    if not WIDE_MULS:
        return [fe8.mul(x, y) for x, y in zip(xs, ys)]
    n = len(xs)
    r = fe8.mul(jnp.concatenate(xs, axis=1), jnp.concatenate(ys, axis=1))
    return jnp.split(r, n, axis=1)


def _sqw(xs):
    if not WIDE_MULS:
        return [fe8.sq(x) for x in xs]
    n = len(xs)
    r = fe8.sq(jnp.concatenate(xs, axis=1))
    return jnp.split(r, n, axis=1)


def ge_dbl_w(p, need_t: bool = True):
    """Dedicated doubling: EFD dbl-2008-hwcd with a = -1, all four output
    coordinates scaled by -1 (a legal uniform projective scaling in
    extended coords) so every term is a plain positive field op — 4
    squarings + 4 muls vs a unified add's 9 muls; complete for every
    input. The 4 squarings / 4 output muls are optionally packed wide.

    need_t=False skips the T3 mul: the first doubling of each ladder
    iteration feeds only the second doubling, which never reads T."""
    x1, y1, z1, _ = p
    # carry schedule (round 4, tests/test_fe8_bounds.py): muls/squares
    # carry 3 passes (limbs < 712); sums that feed a multiply use add_c
    # (one pass); differences that feed a multiply use sub1 (one pass,
    # < 1054) — every multiply input stays < MUL_INPUT_BOUND = 1349
    a, b, zz, e0 = _sqw([x1, y1, z1, fe8.add_c(x1, y1)])
    c = fe8.add(zz, zz)
    s1 = fe8.add_c(a, b)
    e = fe8.sub1(e0, s1)
    g = fe8.sub1(b, a)
    f = fe8.sub1(c, g)
    if need_t:
        x3, y3, z3, t3 = _mulw([e, g, f, e], [f, s1, g, s1])
    else:
        x3, y3, z3 = _mulw([e, g, f], [f, s1, g])
        t3 = None
    return (x3, y3, z3, t3)


def to_cached(q):
    """(X,Y,Z,T) -> cached (Y+X, Y-X, 2Z, 2dT) — the ref10 ge_cached
    format: a cached-operand addition then needs only 2 wide muls.
    All four components are multiply operands downstream, so the sums
    carry once (add_c/sub1)."""
    x, y, z, t = q
    return (fe8.add_c(y, x), fe8.sub1(y, x), fe8.add_c(z, z),
            fe8.mul(t, D2))


def ge_add_cached(p, cq):
    """Complete addition of a cached-format operand: 2 wide muls."""
    x1, y1, z1, t1 = p
    yx2, ym2, z22, t2d = cq
    a, b, c, d2 = _mulw([fe8.sub1(y1, x1), fe8.add_c(y1, x1), t1, z1],
                        [ym2, yx2, t2d, z22])
    e = fe8.sub1(b, a)
    f = fe8.sub1(d2, c)
    g = fe8.add_c(d2, c)
    h = fe8.add_c(b, a)
    x3, y3, z3, t3 = _mulw([e, g, f, e], [f, h, g, h])
    return (x3, y3, z3, t3)


def _bits_le(limbs8):
    """(32,B) byte limbs -> (256,B) bits, little-endian bit order."""
    shifts = np.arange(8, dtype=np.int32).reshape(1, 8, 1)
    b = (limbs8[:, None, :] >> shifts) & 1
    return b.reshape(256, limbs8.shape[-1])


def compress(p):
    """Canonical 32-byte encoding: y with sign(x) in the top bit.
    Returns (32,B) exact byte limbs."""
    x, y, z, _ = p
    zi = fe8.invert(z)
    xa = fe8.to_canonical(fe8.mul(x, zi))
    ya = fe8.to_canonical(fe8.mul(y, zi))
    sign = xa[0] & 1
    return ya.at[31].add(sign << 7)


def _win2_msb(limbs8):
    """(32,B) byte limbs -> (127,B) 2-bit windows, msb-first, covering
    bits 0..253. S and k are canonical (< L < 2^253), so bits 253..255
    are zero: the top window pairs (bit 253, bit 252) and only its low
    position (bit 252) can be set."""
    bits = _bits_le(limbs8)[:254]            # (254,B) lsb-first
    lo = bits[0::2]                          # even bit positions
    hi = bits[1::2]
    return (2 * hi + lo)[::-1]               # (127,B) msb-first


def double_scalarmult_w2(s_bytes, k_bytes, neg_a):
    """[S]B + [k](-A) with a 2-bit combined Shamir window: a 16-entry
    table T[i,j] = [i]B + [j](-A) selected per window by arithmetic
    one-hot. 127 iterations of (2 doublings + 1 add) ≈ 381 point ops
    vs the 1-bit ladder's 506 — fewer field muls, same completeness
    (the unified add law covers every table combination)."""
    bsz = s_bytes.shape[-1]

    nax, nay = neg_a
    one = jnp.broadcast_to(fe8.ONE, (32, bsz))
    a1 = (nax, nay, one, fe8.mul(nax, nay))
    a2 = ge_dbl_w(a1)
    a3 = ge_add_cached(a2, to_cached(a1))
    p_ident = tuple(jnp.broadcast_to(c, (32, bsz)) for c in IDENT)
    a_mults = [p_ident, a1, a2, a3]
    b_mults = [p_ident] + [
        tuple(jnp.broadcast_to(c, (32, bsz)) for c in _BASE_MULTS[m])
        for m in (1, 2, 3)]

    # T[i + 4j] = [i]B + [j](-A) in cached format; i=0 or j=0 rows need no
    # extra adds
    table = []
    for j in range(4):
        cached_aj = to_cached(a_mults[j])
        for i in range(4):
            if i == 0:
                table.append(cached_aj)
            elif j == 0:
                table.append(to_cached(b_mults[i]))
            else:
                table.append(to_cached(ge_add_cached(b_mults[i],
                                                     cached_aj)))
    # (16, 4, 32, B) stacked once so the scan body reads one array
    table_arr = jnp.stack([jnp.stack(t) for t in table])

    sw = _win2_msb(s_bytes)                  # (127,B) values 0..3
    kw = _win2_msb(k_bytes)

    def body(p, wins):
        ws, wk = wins                        # (B,) int32 each
        p = ge_dbl_w(ge_dbl_w(p, need_t=False))
        idx = ws + 4 * wk                    # (B,) 0..15
        # arithmetic one-hot select, no gather (XLA-friendly)
        sel = (idx[None, :] ==
               jnp.arange(16, dtype=jnp.int32)[:, None])  # (16,B)
        q_all = jnp.einsum("tclb,tb->clb", table_arr,
                           sel.astype(jnp.int32))
        q = (q_all[0], q_all[1], q_all[2], q_all[3])
        return ge_add_cached(p, q), None

    zero = jnp.zeros_like(s_bytes)
    p0 = (zero, zero + fe8.ONE, zero + fe8.ONE, zero)
    p_fin, _ = lax.scan(body, p0, (sw, kw), unroll=SCAN_UNROLL)
    return p_fin


def verify_kernel(s_bytes, k_bytes, neg_ax, neg_ay, r_bytes):
    """Device entry: all args (32,B) int32 byte limbs. Returns (B,) bool
    equation-match (host flags are ANDed outside)."""
    p = double_scalarmult_w2(s_bytes, k_bytes, (neg_ax, neg_ay))
    enc = compress(p)
    return fe8.eq_canonical(enc, r_bytes)


# ---------------------------------------------------------------------------
# v2: full-on-device pipeline — point decompression + strict byte checks on
# the TPU, so the (single-core) host only computes k = SHA512(R‖A‖M) mod L.
# Inputs travel as uint8 (B,32) arrays: 128 B/signature instead of the 2.6 KB
# an int32 limb layout would ship over the host link.
# Semantics: bit-identical to ed25519_ref.verify / libsodium strict
# (crypto/SecretKey.cpp:427-460): canonical S/A/R, small-order A/R rejected,
# cofactorless equation.
# ---------------------------------------------------------------------------

_P_BYTES = [(( _ref.P >> (8 * i)) & 0xFF) for i in range(32)]
_L_BYTES = [(( _ref.L >> (8 * i)) & 0xFF) for i in range(32)]
SQRT_M1 = fe8.const(_ref.SQRT_M1)

# Canonical y-coordinates of the 8-torsion (identity, order-2, the two
# order-4 points share y=0, and the two order-8 y values); a canonical
# encoding is small-order iff its y is in this set (both x signs are
# torsion). Derived from the oracle at import.
_TORSION_Y = [0, 1, _ref.P - 1]
for _enc in ("26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05",
             "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a"):
    _pt = _ref.pt_decompress(bytes.fromhex(_enc), strict=True)
    assert _pt is not None and _ref.pt_is_small_order(_pt)
    _TORSION_Y.append(_pt[1] % _ref.P)
_TORSION_Y_BYTES = np.array(
    [[(y >> (8 * i)) & 0xFF for i in range(32)] for y in sorted(_TORSION_Y)],
    dtype=np.int32)                                   # (5, 32)


def _lt_const(b, const_bytes):
    """(B,) bool — little-endian byte array b (32,B) < the 32-byte constant."""
    lt = jnp.zeros(b.shape[-1], dtype=bool)
    eq = jnp.ones(b.shape[-1], dtype=bool)
    for i in range(31, -1, -1):
        c = const_bytes[i]
        lt = lt | (eq & (b[i] < c))
        eq = eq & (b[i] == c)
    return lt


def _is_torsion_y(y):
    """(B,) bool — canonical y bytes match one of the 5 torsion y values."""
    t = jnp.asarray(_TORSION_Y_BYTES)                # (5,32)
    return jnp.any(jnp.all(t[:, :, None] == y[None, :, :], axis=1), axis=0)


def _pow_p58(z):
    """z^((p-5)/8) = z^(2^252 - 3) — ref10 pow22523 chain."""
    t0 = fe8.sq(z)                     # 2
    t1 = fe8.nsquare(t0, 2)            # 8
    t1 = fe8.mul(z, t1)                # 9
    t0 = fe8.mul(t0, t1)               # 11
    t0 = fe8.sq(t0)                    # 22
    t0 = fe8.mul(t1, t0)               # 31 = 2^5 - 1
    t1 = fe8.nsquare(t0, 5)
    t0 = fe8.mul(t1, t0)               # 2^10 - 1
    t1 = fe8.nsquare(t0, 10)
    t1 = fe8.mul(t1, t0)               # 2^20 - 1
    t2 = fe8.nsquare(t1, 20)
    t1 = fe8.mul(t2, t1)               # 2^40 - 1
    t1 = fe8.nsquare(t1, 10)
    t0 = fe8.mul(t1, t0)               # 2^50 - 1
    t1 = fe8.nsquare(t0, 50)
    t1 = fe8.mul(t1, t0)               # 2^100 - 1
    t2 = fe8.nsquare(t1, 100)
    t1 = fe8.mul(t2, t1)               # 2^200 - 1
    t1 = fe8.nsquare(t1, 50)
    t0 = fe8.mul(t1, t0)               # 2^250 - 1
    t0 = fe8.nsquare(t0, 2)            # 2^252 - 4
    return fe8.mul(t0, z)              # 2^252 - 3


def decompress_neg(y_bytes, sign):
    """Strict decompression of (y, sign) with the result negated:
    returns (neg_x, y, valid) where neg_x is -x as loose limbs. Mirrors
    ed25519_ref._recover_x; total (branch-free) on invalid input."""
    y = fe8.from_bytes(y_bytes)
    y2 = fe8.sq(y)
    one = jnp.broadcast_to(fe8.ONE, y.shape)
    u = fe8.sub1(y2, one)                      # y^2 - 1
    v = fe8.add_c(fe8.mul(fe8.D, y2), one)     # d y^2 + 1
    v2 = fe8.sq(v)
    v3 = fe8.mul(v2, v)
    uv3 = fe8.mul(u, v3)
    uv7 = fe8.mul(uv3, fe8.sq(v2))             # u v^7
    x = fe8.mul(uv3, _pow_p58(uv7))            # candidate root
    vx2 = fe8.mul(v, fe8.sq(x))
    # v x^2 == +-u, each via one canonicalized difference/sum
    root_ok = fe8.is_zero_canonical(
        fe8.to_canonical(fe8.sub1(vx2, u)))
    root_flip = fe8.is_zero_canonical(
        fe8.to_canonical(fe8.add_c(vx2, u)))
    x = jnp.where(root_flip, fe8.mul(x, SQRT_M1), x)
    valid = root_ok | root_flip
    x_c = fe8.to_canonical(x)
    x_is_zero = fe8.is_zero_canonical(x_c)
    valid = valid & ~(x_is_zero & (sign == 1))  # "-0" is invalid
    # apply the sign bit, then negate: A = (x_signed, y), -A = (p-x_signed, y)
    flip = (x_c[0] & 1) != sign
    zero = jnp.zeros_like(x_c)
    x_signed = jnp.where(flip, fe8.sub1(zero, x_c), x_c)
    neg_x = fe8.sub1(zero, x_signed)
    return neg_x, y, valid


def verify_kernel_full(a_u8, r_u8, s_u8, k_u8):
    """Device entry v2: (B,32) uint8 arrays (A enc, R enc, S, k). Returns
    (B,) bool — the complete strict verdict, no host flags needed."""
    return _verify_full(a_u8.astype(jnp.int32).T, r_u8.astype(jnp.int32).T,
                        s_u8.astype(jnp.int32).T, k_u8.astype(jnp.int32).T)


def verify_kernel_msg32(a_u8, r_u8, s_u8, m_u8):
    """Device entry v3: like verify_kernel_full but takes the raw 32-byte
    message instead of k — k = SHA512(R‖A‖M) mod L is computed on device
    (ops/sha512.py), removing the last per-signature host work for the
    tx-hash hot path (fixed 32-byte contents hash, SURVEY.md §3.2;
    reference: transactions/TransactionFrame.cpp:99-107)."""
    from . import sha512 as _sha
    k_b = _sha.k_mod_l_96(r_u8, a_u8, m_u8)       # (32,B) exact bytes
    return _verify_full(a_u8.astype(jnp.int32).T, r_u8.astype(jnp.int32).T,
                        s_u8.astype(jnp.int32).T, k_b)


def _verify_full(a_b, r_b, s_b, k_b):
    """Shared v2/v3 body: (32,B) int32 byte limbs of A enc, R enc, S, k."""
    s_ok = _lt_const(s_b, _L_BYTES)
    sign_a = a_b[31] >> 7
    y_a = a_b.at[31].set(a_b[31] & 0x7F)
    a_canon = _lt_const(y_a, _P_BYTES)
    a_small = _is_torsion_y(y_a)
    y_r = r_b.at[31].set(r_b[31] & 0x7F)
    r_canon = _lt_const(y_r, _P_BYTES)
    r_small = _is_torsion_y(y_r)

    neg_ax, ay, a_valid = decompress_neg(y_a, sign_a)
    p = double_scalarmult_w2(s_b, k_b, (neg_ax, ay))
    enc = compress(p)
    eq = fe8.eq_canonical(enc, r_b)
    return (eq & s_ok & a_canon & ~a_small & a_valid
            & r_canon & ~r_small)
