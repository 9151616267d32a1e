"""Batch SHA-512 + exact mod-L reduction on device (TPU, JAX/XLA).

Closes the last host-side per-signature cost in the verify pipeline:
k = SHA512(R‖A‖M) mod L was computed by one host core, per signature,
whatever the kernel's speed. For the dominant workload — transaction signatures,
which verify over a fixed 32-byte contents hash (SURVEY.md §3.2
"message shapes"; reference: transactions/TransactionFrame.cpp:99-107)
— R‖A‖M is exactly 96 bytes, one SHA-512 block after padding, with a
compile-time-constant layout. So the whole prep moves on device and the
host ships raw (A, R, S, M) bytes only.

TPU-first design:
- SHA-512's 64-bit words are (hi, lo) uint32 pairs — the VPU has no
  64-bit lanes. rotr/shr are shift/or pairs; 64-bit add is two uint32
  adds plus an unsigned-compare carry. All ops are elementwise over the
  batch (lane) axis: 80 unrolled rounds of straight-line vector code,
  zero control flow, fused by XLA.
- The 512-bit digest is reduced mod L (the edwards25519 group order)
  with byte-limb arithmetic matching fe8's layout: a table fold
  digest ≡ lo₃₂ + Σ d_{32+i}·(256^{32+i} mod L), repeated until the
  value fits 32 exact byte limbs, then four conditional subtractions
  of 8L/4L/2L/L. Exact reduction is semantics-critical: for a public
  key with a torsion component [k]A ≠ [k mod L]A, and libsodium
  (crypto/SecretKey.cpp:427-460 path → sc_reduce) uses k mod L.

Differentially tested against hashlib.sha512 and the pure-python oracle
(tests/test_tpu_verifier.py::TestDeviceSha).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

L = 2**252 + 27742317777372353535851937790883648493

# SHA-512 round constants as (hi, lo) uint32 pairs
_K = [
    0x428a2f98d728ae22, 0x7137449123ef65cd, 0xb5c0fbcfec4d3b2f, 0xe9b5dba58189dbbc,
    0x3956c25bf348b538, 0x59f111f1b605d019, 0x923f82a4af194f9b, 0xab1c5ed5da6d8118,
    0xd807aa98a3030242, 0x12835b0145706fbe, 0x243185be4ee4b28c, 0x550c7dc3d5ffb4e2,
    0x72be5d74f27b896f, 0x80deb1fe3b1696b1, 0x9bdc06a725c71235, 0xc19bf174cf692694,
    0xe49b69c19ef14ad2, 0xefbe4786384f25e3, 0x0fc19dc68b8cd5b5, 0x240ca1cc77ac9c65,
    0x2de92c6f592b0275, 0x4a7484aa6ea6e483, 0x5cb0a9dcbd41fbd4, 0x76f988da831153b5,
    0x983e5152ee66dfab, 0xa831c66d2db43210, 0xb00327c898fb213f, 0xbf597fc7beef0ee4,
    0xc6e00bf33da88fc2, 0xd5a79147930aa725, 0x06ca6351e003826f, 0x142929670a0e6e70,
    0x27b70a8546d22ffc, 0x2e1b21385c26c926, 0x4d2c6dfc5ac42aed, 0x53380d139d95b3df,
    0x650a73548baf63de, 0x766a0abb3c77b2a8, 0x81c2c92e47edaee6, 0x92722c851482353b,
    0xa2bfe8a14cf10364, 0xa81a664bbc423001, 0xc24b8b70d0f89791, 0xc76c51a30654be30,
    0xd192e819d6ef5218, 0xd69906245565a910, 0xf40e35855771202a, 0x106aa07032bbd1b8,
    0x19a4c116b8d2d0c8, 0x1e376c085141ab53, 0x2748774cdf8eeb99, 0x34b0bcb5e19b48a8,
    0x391c0cb3c5c95a63, 0x4ed8aa4ae3418acb, 0x5b9cca4f7763e373, 0x682e6ff3d6b2b8a3,
    0x748f82ee5defb2fc, 0x78a5636f43172f60, 0x84c87814a1f0ab72, 0x8cc702081a6439ec,
    0x90befffa23631e28, 0xa4506cebde82bde9, 0xbef9a3f7b2c67915, 0xc67178f2e372532b,
    0xca273eceea26619c, 0xd186b8c721c0c207, 0xeada7dd6cde0eb1e, 0xf57d4f7fee6ed178,
    0x06f067aa72176fba, 0x0a637dc5a2c898a6, 0x113f9804bef90dae, 0x1b710b35131c471b,
    0x28db77f523047d84, 0x32caab7b40c72493, 0x3c9ebe0a15c9bebc, 0x431d67c49c100d4c,
    0x4cc5d4becb3e42b6, 0x597f299cfc657e2a, 0x5fcb6fab3ad6faec, 0x6c44198c4a475817,
]
_IV = [
    0x6a09e667f3bcc908, 0xbb67ae8584caa73b, 0x3c6ef372fe94f82b, 0xa54ff53a5f1d36f1,
    0x510e527fade682d1, 0x9b05688c2b3e6c1f, 0x1f83d9abfb41bd6b, 0x5be0cd19137e2179,
]


def _split(c: int):
    return np.uint32(c >> 32), np.uint32(c & 0xFFFFFFFF)


def _add2(ah, al, bh, bl):
    """(a + b) mod 2^64 on (hi, lo) uint32 pairs."""
    lo = al + bl
    hi = ah + bh + (lo < al).astype(jnp.uint32)
    return hi, lo


def _rotr(h, l, n: int):
    n &= 63
    if n == 0:
        return h, l
    if n == 32:
        return l, h
    if n < 32:
        return ((h >> n) | (l << (32 - n)), (l >> n) | (h << (32 - n)))
    m = n - 32
    return ((l >> m) | (h << (32 - m)), (h >> m) | (l << (32 - m)))


def _shr(h, l, n: int):
    # n < 32 everywhere it is used (7 and 6)
    return h >> n, (l >> n) | (h << (32 - n))


def _xor3(a, b, c):
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _big_sigma0(h, l):
    return _xor3(_rotr(h, l, 28), _rotr(h, l, 34), _rotr(h, l, 39))


def _big_sigma1(h, l):
    return _xor3(_rotr(h, l, 14), _rotr(h, l, 18), _rotr(h, l, 41))


def _small_sigma0(h, l):
    return _xor3(_rotr(h, l, 1), _rotr(h, l, 8), _shr(h, l, 7))


def _small_sigma1(h, l):
    return _xor3(_rotr(h, l, 19), _rotr(h, l, 61), _shr(h, l, 6))


_K_ARR = np.array([[k >> 32, k & 0xFFFFFFFF] for k in _K], dtype=np.uint32)


# Scan-unroll factor for the 80 compression rounds: the sweet spot
# between compile time (fully unrolled ≈5k serially-dependent uint32 ops
# send XLA CPU past 9 minutes and stall the chip compile too) and
# scan-step overhead (each step copies the (16,2,B) schedule ring).
# Factors of 80 only; other factors are not measured on the chip.
SHA_UNROLL = 8


def sha512_96(r_u8, a_u8, m_u8):
    """Batch SHA-512 of the 96-byte message R‖A‖M (each (B,32) uint8).
    One block, compile-time-constant padding. Returns the digest as
    (64, B) int32 byte limbs in *little-endian byte position order*
    (d[0] = first digest byte), ready for mod-L reduction.

    The 80 rounds use the classic rolling 16-word schedule (W[t+16] is
    produced every step; it is first read at step t+16, so the
    recurrence is uniform over all 80 steps) as a lax.scan with
    SHA_UNROLL-chunked steps."""
    bsz = r_u8.shape[0]
    msg = jnp.concatenate([r_u8, a_u8, m_u8], axis=1).astype(jnp.uint32).T
    # (96, B) big-endian byte stream -> 12 (hi, lo) word pairs
    w = []
    for i in range(12):
        b8 = [msg[8 * i + j] for j in range(8)]
        hi = (b8[0] << 24) | (b8[1] << 16) | (b8[2] << 8) | b8[3]
        lo = (b8[4] << 24) | (b8[5] << 16) | (b8[6] << 8) | b8[7]
        w.append((hi, lo))
    # derive constants from the input so every scan-carry leaf shares the
    # input's device-varying type under shard_map (a replicated initial
    # carry vs a varying computed carry is a TypeError there)
    zero = msg[0] ^ msg[0]
    pad_h = zero + np.uint32(0x80000000)
    w.append((pad_h, zero))                       # byte 96 = 0x80
    w.append((zero, zero))
    w.append((zero, zero))
    w.append((zero, zero + np.uint32(96 * 8)))

    state = []
    for c in _IV:
        ch, cl = _split(c)
        state.append((zero + ch, zero + cl))

    def round_math(vars8, wh, wl, kh, kl):
        a, b, c_, d, e, f, g, hh = vars8
        ch = ((e[0] & f[0]) ^ (~e[0] & g[0]),
              (e[1] & f[1]) ^ (~e[1] & g[1]))
        t1 = _add2(*hh, *_big_sigma1(*e))
        t1 = _add2(*t1, *ch)
        t1 = _add2(*t1, kh, kl)
        t1 = _add2(*t1, wh, wl)
        maj = ((a[0] & b[0]) ^ (a[0] & c_[0]) ^ (b[0] & c_[0]),
               (a[1] & b[1]) ^ (a[1] & c_[1]) ^ (b[1] & c_[1]))
        t2 = _add2(*_big_sigma0(*a), *maj)
        e_n = _add2(*d, *t1)
        a_n = _add2(*t1, *t2)
        return (a_n, a, b, c_, e_n, e, f, g)

    def next_w(w_t, w_t1, w_t9, w_t14):
        # W[t+16] = σ1(W[t+14]) + W[t+9] + σ0(W[t+1]) + W[t]
        s0 = _small_sigma0(*w_t1)
        s1 = _small_sigma1(*w_t14)
        nw = _add2(*w_t, *w_t9)
        nw = _add2(*nw, *s0)
        return _add2(*nw, *s1)

    # carry = (vars8, 16-pair W ring) as TUPLES: rotating a tuple is
    # SSA renaming, so the scan body materializes no (16,2,B) ring
    # copy and no (8,2,B) state stack per round (the stacked-array
    # form measured ~60 ms of pure data movement per 16384-batch; a
    # fully unrolled emission sent XLA CPU compile past 9 minutes)
    def round_body(carry, kt):
        vars8, wv = carry
        wt = wv[0]
        out = round_math(vars8, wt[0], wt[1],
                         jnp.broadcast_to(kt[0], wt[0].shape),
                         jnp.broadcast_to(kt[1], wt[1].shape))
        nw = next_w(wt, wv[1], wv[9], wv[14])
        return (out, wv[1:] + (nw,)), None

    (st_pairs, _), _ = lax.scan(round_body, (tuple(state), tuple(w)),
                                jnp.asarray(_K_ARR), unroll=SHA_UNROLL)

    final = []
    for init, fin in zip(state, st_pairs):
        final.append(_add2(*init, *fin))

    # digest words (big-endian per word) -> little-endian byte positions
    limbs = []
    for vh, vl in final:
        for word in (vh, vl):
            for shift in (24, 16, 8, 0):
                limbs.append(((word >> shift) & 0xFF).astype(jnp.int32))
    return jnp.stack(limbs)                       # (64, B)


# --- mod-L reduction ---------------------------------------------------------

def _le_limbs(v: int, n: int) -> np.ndarray:
    return np.array([(v >> (8 * i)) & 0xFF for i in range(n)], dtype=np.int32)

# 256^(32+i) mod L for i in 0..31, as (32, 32) int32: row i = byte limbs
_POW_TAB = np.stack([_le_limbs(pow(256, 32 + i, L), 32) for i in range(32)])

# 8L, 4L, 2L, L as 33-limb arrays (8L has bit 255 set; 33 limbs keep the
# "add (2^264 - C)" conditional-subtract trick uniform)
_SUB_CONSTS = [_le_limbs((2**264 - m * L), 33) for m in (8, 4, 2, 1)]


def _seq_carry_ext(c):
    """Exact sequential byte carry over (32, B); returns (limbs, carry)."""
    outs = []
    carry = jnp.zeros_like(c[0])
    for i in range(32):
        t = c[i] + carry
        outs.append(t & 0xFF)
        carry = t >> 8
    return jnp.stack(outs), carry


def mod_l(d_limbs):
    """(64, B) int32 byte limbs (little-endian 512-bit value) -> (32, B)
    exact byte limbs of the value mod L.

    Fold 1: v = lo32 + Σ d[32+i]·(256^(32+i) mod L). Each accumulated
    limb < 255 + 32·255·255 < 2^21.1, so v < 2^269.1 and fits int32.
    Folds 2..n: sequential-carry to exact bytes + carry-out c < 2^14,
    then v = bytes + c0·(2^256 mod L) + c1·(2^264 mod L); each fold
    shrinks the value by ~3 bits (2^256 mod L ≈ 2^252.9), so after five
    the carry-out is 0 and v < 2^256 in exact byte limbs. Final: four
    conditional subtractions of 8L/4L/2L/L bring v < L (v/L < 16)."""
    tab = jnp.asarray(_POW_TAB)                   # (32, 32)
    lo = d_limbs[:32]
    hi = d_limbs[32:]                             # (32, B)
    acc = lo + jnp.einsum("ij,ib->jb", tab, hi)
    for _ in range(5):
        bytes_, carry = _seq_carry_ext(acc)
        c0 = carry & 0xFF
        c1 = carry >> 8
        acc = bytes_ + c0 * tab[0][:, None] + c1 * tab[1][:, None]
    v, carry = _seq_carry_ext(acc)                # carry == 0 now
    for const33 in _SUB_CONSTS:
        cst = jnp.asarray(const33[:, None])
        t = v + cst[:32]
        outs = []
        c = jnp.zeros_like(t[0])
        for i in range(32):
            s = t[i] + c
            outs.append(s & 0xFF)
            c = s >> 8
        c = c + cst[32]
        borrow_free = (c >> 8) > 0                # v + (2^264 - mL) >= 2^264
        tv = jnp.stack(outs)
        v = jnp.where(borrow_free, tv, v)
    return v


def k_mod_l_96(r_u8, a_u8, m_u8):
    """k = SHA512(R‖A‖M) mod L for 32-byte messages, fully on device.
    Returns (32, B) int32 exact byte limbs (the layout verify_kernel_full
    uses for scalars)."""
    return mod_l(sha512_96(r_u8, a_u8, m_u8))
