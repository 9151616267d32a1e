"""The adversarial corpus of 32-byte-message Ed25519 tuples, with the
verdict of the pure-Python oracle for each: everything the strict
verifier's rejection surface distinguishes.

Copied and narrowed from `stellar_core_tpu/ops/testvectors.py` (which
`scripts/tpu_differential.py` runs on the chip): only 32-byte messages,
because a replayed checkpoint's batch goes through `verify_kernel_msg32`;
keys, signatures and verdicts come from the oracle of this directory,
not from the program; everything is drawn from the run's seed.

  - valid signatures; flipped bits in R, S, the message, the public key
  - S = 0, S = L, S + L (non-canonical), S = 2^256 - 1
  - non-canonical encodings of A and R (y >= p, all FF)
  - small-order (8-torsion) A and R, the identity among them
  - torsion-defect keys A' = A + T8: where cofactorless and cofactored
    verification disagree
"""

import hashlib

from benchmark.reference import ed25519_oracle as ref


def _small_order_points() -> list:
    seen = {}
    i = 0
    while len(seen) < 8 and i < 4000:
        q = ref.pt_decompress(hashlib.sha256(b"torsion%d" % i).digest(),
                              strict=False)
        i += 1
        if q is None:
            continue
        t = ref.pt_mul(ref.L, q)
        if ref.pt_is_small_order(t):
            seen[ref.pt_compress(t)] = t
    return list(seen.keys())


def corpus(seed: int, n_random: int) -> list:
    """[(public key, signature, message, oracle's verdict)]."""
    secrets = [hashlib.sha256(b"adversarial-key-%d-%d" % (seed, i)).digest()
               for i in range(4)]
    keys = [(s, ref.secret_to_public(s)) for s in secrets]
    items = []
    for i in range(n_random):
        sec, pub = keys[i % len(keys)]
        msg = hashlib.sha256(b"adversarial-msg-%d-%d" % (seed, i)).digest()
        sig = ref.sign(sec, msg)
        k = i % 8
        if k == 4:      # flip a bit of R
            sig = bytes([sig[0] ^ 0x40]) + sig[1:]
        elif k == 5:    # flip a low bit of S (stays canonical)
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        elif k == 6:    # flip a bit of the message
            msg = bytes([msg[0] ^ 0x80]) + msg[1:]
        elif k == 7:    # flip a bit of the public key
            pub = bytes([pub[0] ^ 2]) + pub[1:]
        items.append((pub, sig, msg))

    sec, pub = keys[0]
    msg = hashlib.sha256(b"adversarial-%d" % seed).digest()
    sig = ref.sign(sec, msg)
    r, s = sig[:32], sig[32:]
    s_val = int.from_bytes(s, "little")
    items.append((pub, r + bytes(32), msg))                       # S = 0
    items.append((pub, r + ref.L.to_bytes(32, "little"), msg))    # S = L
    items.append((pub, r + (s_val + ref.L).to_bytes(32, "little"), msg))
    items.append((pub, r + b"\xff" * 32, msg))
    for enc in ((ref.P + 1).to_bytes(32, "little"),
                (ref.P + 2).to_bytes(32, "little"), b"\xff" * 32):
        items.append((enc, sig, msg))
        items.append((pub, enc + s, msg))
    torsion = _small_order_points()
    for t in torsion:
        items.append((t, sig, msg))
        items.append((pub, t + s, msg))
    a = ref.pt_decompress(pub, strict=True)
    for tenc in torsion:
        t = ref.pt_decompress(tenc, strict=False)
        items.append((ref.pt_compress(ref.pt_add(a, t)), sig, msg))
    items.append((pub, sig, msg))
    items.append((pub, sig, msg))
    return [(p, sg, m, bool(ref.verify(p, sg, m))) for p, sg, m in items]
