"""GF(2^255-19) field arithmetic for TPU, radix 2^8, int32 limbs.

Design notes (TPU-first, not a port of any CPU bignum library):

- A field element is an int32 array of shape (32, B): 32 little-endian
  base-256 limbs on the sublane axis, B independent batch elements on the
  lane axis. With B >= 128 every vector op fills full 8x128 VPU tiles, and
  the batch dimension shards cleanly across a device mesh (pure data
  parallelism — signatures have no cross-element dependency).

- Radix 2^8 is chosen so schoolbook products and column sums stay inside
  int32 *without* 64-bit accumulators (TPUs have no native wide-multiply):
  with the loose-limb invariants below, every intermediate is < 2^31.

- Limb-bound contract (round-4 lazy schedule; executable proof in
  tests/test_fe8_bounds.py, narrative in docs/LIMB_WIDTHS.md):
    * rolled (TPU) mul/sq outputs: limbs <= 711 (3 passes; a stable
      fixpoint); scatter (CPU) outputs: < 2^9 (4 passes)
    * sub outputs < 2^9; sub1 outputs <= 1053 (1 pass — only for
      results that feed a multiply or a sub minuend)
    * add_c outputs <= 445 when fed two mul outputs
    * mul/sq accept inputs < MUL_INPUT_BOUND = 1349 (the worst folded
      column is 1179 * B^2, int32-safe up to B = 1349)
    * sub/sub1 subtrahends must stay under the smallest 16p bias limb
      (2033, limb 31) — every in-tree subtrahend is <= 1424

- Carry propagation is a *parallel* pass (shift-by-one-limb via roll on
  the sublane axis, with the wrap-around limb folded by x38 since
  2^256 ≡ 38 mod p) — no sequential 32-step ripple in the hot loop.
  Exact sequential carries are only used in `to_canonical` (once per
  point compression, off the hot loop).

Matches the semantic oracle stellar_core_tpu/crypto/ed25519_ref.py.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

P = 2**255 - 19

# wrap-around fold weight: limb 0 receives carry-out of limb 31 times 38
_FOLD = np.ones((32, 1), dtype=np.int32)
_FOLD[0, 0] = 38

# 16*p in base-256 limbs: per-limb bias >= 1023 everywhere, so
# a + BIAS16P - b is non-negative for any b with limbs < 2^10.
_BIAS16P = np.full((32, 1), 16 * 0xFF, dtype=np.int32)
_BIAS16P[0, 0] = 16 * 0xED
_BIAS16P[31, 0] = 16 * 0x7F


def const(v: int) -> np.ndarray:
    """Python int -> (32,1) canonical limb column (broadcasts over batch)."""
    v %= P
    return np.array([(v >> (8 * i)) & 0xFF for i in range(32)],
                    dtype=np.int32).reshape(32, 1)


ZERO = const(0)
ONE = const(1)
# d = -121665/121666 mod p (twisted Edwards constant)
D = const((-121665 * pow(121666, P - 2, P)) % P)


def from_bytes(b):
    """(32,B) uint8 limbs -> int32 field element (values are the limbs)."""
    return b.astype(jnp.int32)


def carry_pass(c):
    """One parallel carry pass; wrap-around limb folds with weight 38."""
    h = c >> 8
    l = c & 0xFF
    h = jnp.roll(h, 1, axis=0) * _FOLD
    return l + h


def add(a, b):
    """Plain limb add — output limbs < 2^10 when inputs are reduced-loose."""
    return a + b


def add_c(a, b):
    """Add + one carry pass — output < 560, safe wherever < 2^10 is needed
    even when inputs are already sums."""
    return carry_pass(a + b)


def sub(a, b):
    """a - b mod p; b limbs must be < 2^10. Output reduced-loose (< 2^9)."""
    c = a + _BIAS16P - b
    return carry_pass(carry_pass(c))


def sub1(a, b):
    """a - b mod p with a SINGLE carry pass — for results consumed as
    mul/sq inputs or as another sub's minuend, which tolerate limbs up
    to MUL_INPUT_BOUND (1349). Bounds (tests/test_fe8_bounds.py):
    a limbs <= 1424, b limbs per-limb under the 16p bias vector (its
    smallest limb is 2033 at index 31; in-tree subtrahends stay
    <= 1424) give outputs <= 1053. The group-law hot path uses this for
    every difference that feeds a multiply, saving one full-width pass
    per sub versus `sub`."""
    c = a + _BIAS16P - b
    return carry_pass(c)


# mul weight matrix: W[i, k] = 38 where column k received a wrapped
# product (j = k - i + 32, i.e. k < i), else 1 — the 2^256 ≡ 38 fold
# applied inline so no 63-column accumulator ever materializes
_MULW = np.ones((32, 32, 1), dtype=np.int32)
for _i in range(32):
    _MULW[_i, :_i, 0] = 38
del _i


def _use_rolled() -> bool:
    """Pick the mul formulation for the backend this trace targets.

    The rolled-FMA form is the TPU shape (zero dynamic-update-slices;
    the scatter-add form lowers to 32 of them a multiply, not measured
    on today's chip). The XLA *CPU* backend is the
    opposite: it compiles the 32-distinct-roll scan body pathologically
    slowly (minutes per bucket shape vs seconds for the scatter-add
    form), and tests/dryrun always run on the CPU mesh. Decided at
    trace time, so each backend caches its own formulation."""
    import jax
    return jax.default_backend() == "tpu"


def _mul_rolled(a, b):
    """32x32 product with the 2^256≡38 fold inline, THREE carry passes.

    Formulated as 32 fused vector FMAs over rolled copies of b:
        c[k] = sum_i a_i * b_{(k-i) mod 32} * W[i,k]
    (W applies x38 to wrapped columns). This shape matters on TPU: the
    63-column scatter-add version (`c.at[i:i+32].add(...)`) lowered to
    32 dynamic-update-slices PER MULTIPLY, pure data movement;
    rolls + multiply-adds fuse into one elementwise loop instead.

    Carry schedule (round 4): with MUL_INPUT_BOUND = 1349 inputs every
    column stays < 2^31, and interval propagation (see
    tests/test_fe8_bounds.py and docs/LIMB_WIDTHS.md) shows THREE
    passes already bring every limb under 712 — itself a legal mul
    input — so the historical fourth pass was pure waste. The bound
    chain is a stable fixpoint: 711-bounded inputs produce 711-bounded
    outputs."""
    acc = (_MULW[0] * a[0]) * b
    for i in range(1, 32):
        acc = acc + (_MULW[i] * a[i]) * jnp.roll(b, i, axis=0)
    for _ in range(3):
        acc = carry_pass(acc)
    return acc


def _mul_scatter(a, b, bsz):
    """Schoolbook 32x32 -> 63-column product, 2^256≡38 fold, 4 carry
    passes — the CPU-backend formulation (see _use_rolled)."""
    c = jnp.zeros((63, bsz), jnp.int32)
    for i in range(32):
        c = c.at[i:i + 32].add(a[i] * b)
    lo = c[:32]
    lo = lo.at[:31].add(38 * c[32:])
    for _ in range(4):
        lo = carry_pass(lo)
    return lo


def mul(a, b):
    """Field multiply. Inputs: limbs < MUL_INPUT_BOUND (1349). Output:
    rolled (TPU) <= 711, scatter (CPU) < 2^9. Two formulations with
    identical column sums (differential-tested against each other and
    the pure-python oracle); backend picks."""
    bsz = max(a.shape[-1], b.shape[-1])
    a = jnp.broadcast_to(a, (32, bsz))
    b = jnp.broadcast_to(b, (32, bsz))
    if _use_rolled():
        return _mul_rolled(a, b)
    return _mul_scatter(a, b, bsz)


def _sq_scatter(a, bsz):
    """Specialized squaring for the CPU backend: symmetric schoolbook —
    528 limb products instead of 1024. Doubling the accumulated
    off-diagonal half-columns reconstructs exactly the full schoolbook
    column sums, so the bounds contract is identical to mul (columns
    < 32*(2^10-1)^2 < 2^25)."""
    c = jnp.zeros((63, bsz), jnp.int32)
    for i in range(32):
        # off-diagonal partial row: a_i * a_j for j > i
        if i + 1 < 32:
            c = c.at[2 * i + 1:i + 32].add(a[i] * a[i + 1:])
    c = c + c                                    # double off-diagonals
    for i in range(32):
        c = c.at[2 * i].add(a[i] * a[i])         # diagonal
    lo = c[:32]
    lo = lo.at[:31].add(38 * c[32:])
    for _ in range(4):
        lo = carry_pass(lo)
    return lo


def sq(a):
    """Squaring. On TPU: the rolled-FMA mul with both operands equal (a
    528-product symmetric schoolbook only pays off when products are
    scalar ops; in vector form both variants are 32 (32,B) FMAs, and
    its scatter-adds were the data-movement bottleneck). On CPU: the
    symmetric scatter form (half the products, and HLO-identical to
    prior rounds so persistent compile caches stay warm)."""
    if _use_rolled():
        return mul(a, a)
    bsz = a.shape[-1]
    a = jnp.broadcast_to(a, (32, bsz))
    return _sq_scatter(a, bsz)


def nsquare(a, n: int):
    """a^(2^n) via fori_loop (keeps the trace small for long chains)."""
    return lax.fori_loop(0, n, lambda _, x: sq(x), a)


def invert(z):
    """z^(p-2) — the standard curve25519 square-and-multiply chain."""
    t0 = sq(z)                    # 2
    t1 = nsquare(t0, 2)           # 8
    t1 = mul(z, t1)               # 9
    t0 = mul(t0, t1)              # 11
    t2 = sq(t0)                   # 22
    t1 = mul(t1, t2)              # 31 = 2^5-1
    t2 = nsquare(t1, 5)
    t1 = mul(t2, t1)              # 2^10-1
    t2 = nsquare(t1, 10)
    t2 = mul(t2, t1)              # 2^20-1
    t3 = nsquare(t2, 20)
    t2 = mul(t3, t2)              # 2^40-1
    t2 = nsquare(t2, 10)
    t1 = mul(t2, t1)              # 2^50-1
    t2 = nsquare(t1, 50)
    t2 = mul(t2, t1)              # 2^100-1
    t3 = nsquare(t2, 100)
    t2 = mul(t3, t2)              # 2^200-1
    t2 = nsquare(t2, 50)
    t1 = mul(t2, t1)              # 2^250-1
    t1 = nsquare(t1, 5)           # 2^255-2^5
    return mul(t1, t0)            # 2^255-21 = p-2


def _seq_carry(c):
    """Exact sequential base-256 carry; returns (limbs in [0,256), carry)."""
    outs = []
    carry = jnp.zeros_like(c[0])
    for i in range(32):
        t = c[i] + carry
        outs.append(t & 0xFF)
        carry = t >> 8
    return jnp.stack(outs), carry


def to_canonical(c):
    """Fully reduce to the unique representative in [0, p), exact byte
    limbs. Off-hot-loop (used once per compression)."""
    c = carry_pass(carry_pass(c))
    c, top = _seq_carry(c)
    c = c.at[0].add(38 * top)          # 2^256 ≡ 38
    c, top = _seq_carry(c)             # top == 0 now (value < 2^256)
    # fold bit 255 twice: 2^255 ≡ 19
    for _ in range(2):
        b = c[31] >> 7
        c = c.at[31].set(c[31] & 0x7F)
        c = c.at[0].add(19 * b)
        c, _ = _seq_carry(c)
    # value now < 2p: conditionally subtract p once.
    # t = value + 19: bit 255 of t set  <=>  value >= p
    t = c.at[0].add(19)
    t, _ = _seq_carry(t)
    geq = t[31] >> 7                    # 0/1
    t = t.at[31].set(t[31] & 0x7F)      # t - 2^255 = value - p
    return jnp.where(geq.astype(bool), t, c)


def is_zero_canonical(c):
    """(B,) bool — all-limb zero test on a to_canonical() output."""
    return jnp.all(c == 0, axis=0)


def eq_canonical(a, b):
    """(B,) bool — limbwise equality of two canonical encodings."""
    return jnp.all(a == b, axis=0)
