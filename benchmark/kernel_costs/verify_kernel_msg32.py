"""What the algorithm of `verify_kernel_msg32` needs for a batch of
`lanes` signatures: operations and bytes, from the batch shape and the
algorithm, never from XLA's `cost_analysis` (which counts the compiled
program, and a loop body once).

The algorithm (`stellar_core_tpu/ops/ed25519_kernel.py`, `fe8.py`): a
field element is 32 limbs of 8 bits held in int32; per signature

  - strict decompression of A: one exponentiation z^(2^252-3)
    (252 squarings, 11 multiplies) and 10 more multiplies or squarings
  - the 16-entry table [i]B + [j](-A): 1 doubling, 10 additions in
    cached form, 16 conversions to cached form (1 multiply each)
  - the ladder [S]B + [k](-A), 2-bit windows: 127 steps, each two
    doublings (4 squarings + 3 multiplies, then 4 + 4) and one addition
    of a cached point (8 multiplies)
  - compression: one inversion (254 squarings, 11 multiplies), 2
    multiplies

A field multiply is the schoolbook product of 32 x 32 limbs: 1,024
limb multiplies and as many additions (the fold of 2^256 = 38 is one
more multiply per wrapped column and is left out); a squaring needs 528
distinct limb products. Carries, the table select, SHA-512 of R|A|M and
the comparisons are left out: the count is a floor, so the share of the
roofline it yields cannot be flattered by it.

Bytes: the four (lanes, 32) uint8 inputs and one verdict byte per lane;
everything else can live on the chip.
"""

LADDER_STEPS = 127
LIMBS = 32
MUL_OPS = 2 * LIMBS * LIMBS                    # multiply + add per product
SQ_OPS = 2 * (LIMBS * (LIMBS + 1) // 2)


def field_ops_per_signature() -> dict:
    ladder_mul = LADDER_STEPS * (3 + 4 + 8)
    ladder_sq = LADDER_STEPS * (4 + 4)
    decompress_mul, decompress_sq = 11 + 7, 252 + 3
    table_mul = 4 + 10 * 8 + 16            # doubling, additions, cached
    table_sq = 4
    compress_mul, compress_sq = 11 + 2, 254
    return {"multiplies": ladder_mul + decompress_mul + table_mul
            + compress_mul,
            "squarings": ladder_sq + decompress_sq + table_sq + compress_sq}


def operations(lanes: int) -> int:
    f = field_ops_per_signature()
    return lanes * (f["multiplies"] * MUL_OPS + f["squarings"] * SQ_OPS)


def bytes_moved(lanes: int) -> int:
    return lanes * (4 * 32 + 1)


def least_seconds(lanes: int, peaks: dict):
    """(seconds, which bound) for one run at `lanes`: the larger of
    operations over the int32 vector peak and bytes over HBM bandwidth."""
    by_ops = operations(lanes) / peaks["int32_ops_per_s"]["value"]
    by_bytes = bytes_moved(lanes) / peaks["hbm_bytes_per_s"]["value"]
    if by_ops >= by_bytes:
        return by_ops, "int32 vector operations"
    return by_bytes, "HBM bandwidth"
