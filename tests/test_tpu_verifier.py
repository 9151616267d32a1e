"""Differential tests: TPU batch verifier vs the pure-Python oracle.

Mirrors the reference's crypto test tier (crypto/test/CryptoTests.cpp)
plus the extra kernel tier mandated by SURVEY.md §4: RFC-style vectors,
random valid/corrupted batches, strict-rejection edge cases
(non-canonical S/A/R, small-order A/R). The sharded multi-device path
on the virtual 8-device CPU mesh is in test_tpu_verifier_mesh.py: a
file is one work unit of the suite's workers, and the first call of
each kernel shape in a process takes a minute or more on the CPU.
"""

import hashlib

import numpy as np
import pytest

from stellar_core_tpu.crypto import ed25519_ref as ref
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ops import fe8
from stellar_core_tpu.ops.verifier import (TpuBatchVerifier,
                                           ShardedBatchVerifier)


def _mk(n, msg_len=32, seed=0):
    """n (pub, sig, msg) tuples, all valid."""
    items = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(seed * 1000 + i)
        digest = hashlib.sha256(b"msg%d-%d" % (seed, i)).digest()
        msg = (digest * 2)[:msg_len]
        items.append((sk.public_key().raw, sk.sign(msg), msg))
    return items


def _check(verifier, items):
    got = verifier.verify_tuples(items)
    want = [ref.verify(p, s, m) for p, s, m in items]
    assert got == want, (got, want)
    return got


@pytest.fixture(scope="module")
def verifier():
    return TpuBatchVerifier()


# ---------------------------------------------------------------- field ----

def test_fe8_mul_random_vs_python_ints():
    rng = np.random.default_rng(7)
    import jax.numpy as jnp
    B = 8
    # loose limbs up to 2^10-1 (the documented input bound)
    a = rng.integers(0, 1024, size=(32, B), dtype=np.int64).astype(np.int32)
    b = rng.integers(0, 1024, size=(32, B), dtype=np.int64).astype(np.int32)
    c = np.asarray(fe8.mul(jnp.asarray(a), jnp.asarray(b)))
    assert c.max() < 512 and c.min() >= 0, "limb-bound contract violated"
    for j in range(B):
        av = sum(int(a[i, j]) << (8 * i) for i in range(32))
        bv = sum(int(b[i, j]) << (8 * i) for i in range(32))
        cv = sum(int(c[i, j]) << (8 * i) for i in range(32))
        assert cv % ref.P == (av * bv) % ref.P


def test_fe8_sub_invert_canonical():
    import jax.numpy as jnp
    rng = np.random.default_rng(8)
    B = 8
    a = rng.integers(0, 1024, size=(32, B), dtype=np.int64).astype(np.int32)
    b = rng.integers(0, 1024, size=(32, B), dtype=np.int64).astype(np.int32)
    s = np.asarray(fe8.sub(jnp.asarray(a), jnp.asarray(b)))
    inv = np.asarray(fe8.to_canonical(fe8.invert(jnp.asarray(a))))
    for j in range(B):
        av = sum(int(a[i, j]) << (8 * i) for i in range(32))
        bv = sum(int(b[i, j]) << (8 * i) for i in range(32))
        sv = sum(int(s[i, j]) << (8 * i) for i in range(32))
        iv = sum(int(inv[i, j]) << (8 * i) for i in range(32))
        assert sv % ref.P == (av - bv) % ref.P
        assert iv == pow(av % ref.P, ref.P - 2, ref.P)
        assert iv < ref.P


def test_fe8_to_canonical_edges():
    import jax.numpy as jnp
    # values straddling p: p-1, p, p+1, 2p-1, 0, and a loose encoding
    for v in (0, 1, ref.P - 1, ref.P, ref.P + 1, 2 * ref.P - 1, 19, 38):
        limbs = np.array([[(v >> (8 * i)) & 0xFF] for i in range(32)],
                         dtype=np.int32)
        got = np.asarray(fe8.to_canonical(jnp.asarray(limbs)))
        gv = sum(int(got[i, 0]) << (8 * i) for i in range(32))
        assert gv == v % ref.P, v


# --------------------------------------------------------------- verify ----

def test_valid_batch(verifier):
    assert all(_check(verifier, _mk(5)))


def test_corrupted_batches(verifier):
    items = _mk(6, seed=1)
    bad = []
    for i, (p, s, m) in enumerate(items):
        if i % 3 == 0:   # flip a sig byte
            s = bytes([s[0] ^ 1]) + s[1:]
        elif i % 3 == 1:  # flip a msg byte
            m = bytes([m[0] ^ 0x80]) + m[1:]
        else:             # wrong pubkey
            p = SecretKey.pseudo_random_for_testing(999).public_key().raw
        bad.append((p, s, m))
    assert not any(_check(verifier, bad))


def test_mixed_valid_invalid(verifier):
    items = _mk(4, seed=2)
    p, s, m = items[2]
    items[2] = (p, s[:32] + bytes(32), m)  # S = 0: fails the equation
    got = _check(verifier, items)
    assert got == [True, True, False, True]


def test_noncanonical_s_rejected(verifier):
    p, s, m = _mk(1, seed=3)[0]
    s_val = int.from_bytes(s[32:], "little")
    s_plus_l = (s_val + ref.L).to_bytes(32, "little")
    _check(verifier, [(p, s[:32] + s_plus_l, m)])  # oracle says False


def test_noncanonical_a_r_rejected(verifier):
    p, s, m = _mk(1, seed=4)[0]
    # y >= p encodings: p+1 with bit pattern; also all-FF
    bad_enc = (ref.P + 1).to_bytes(32, "little")
    _check(verifier, [(bad_enc, s, m),
                      (p, bad_enc + s[32:], m),
                      (b"\xff" * 32, s, m)])


def test_small_order_a_r_rejected(verifier):
    # build a small-order point: [L]Q for a random curve point Q kills the
    # prime-order component, leaving pure 8-torsion
    small = None
    for i in range(40):
        q = ref.pt_decompress(hashlib.sha256(b"so%d" % i).digest(),
                              strict=True)
        if q is None:
            continue
        t = ref.pt_mul(ref.L, q)
        if ref.pt_is_small_order(t):
            small = ref.pt_compress(t)
            break
    assert small is not None
    p, s, m = _mk(1, seed=5)[0]
    _check(verifier, [(small, s, m), (p, small + s[32:], m)])


def test_identity_encoding_rejected(verifier):
    p, s, m = _mk(1, seed=6)[0]
    ident = ref.pt_compress(ref.IDENTITY)
    _check(verifier, [(ident, s, m), (p, ident + s[32:], m)])


def test_variable_msg_lengths(verifier):
    items = []
    for i, ln in enumerate((0, 1, 31, 32, 33, 100, 1000)):
        sk = SecretKey.pseudo_random_for_testing(7000 + i)
        msg = bytes(range(256)) * 4
        msg = msg[:ln]
        items.append((sk.public_key().raw, sk.sign(msg), msg))
    assert all(_check(verifier, items))


def test_batch_padding_edges(verifier):
    # batch of 1 and a batch crossing a bucket boundary (9 > MIN_BUCKET=8)
    assert all(_check(verifier, _mk(1, seed=8)))
    assert all(_check(verifier, _mk(9, seed=9)))


def test_pallas_ladder_interpret_matches_oracle():
    """The experimental Pallas ladder (interpret mode) agrees with the
    XLA kernel's equation check on valid + corrupted prepared inputs."""
    import numpy as np
    from stellar_core_tpu.ops import ed25519_pallas as ep
    from stellar_core_tpu.ops.verifier import host_prepare

    items = _mk(8, seed=9)
    pubs = np.frombuffer(b"".join(p for p, _, _ in items),
                         dtype=np.uint8).reshape(-1, 32).copy()
    sigs = np.frombuffer(b"".join(s for _, s, _ in items),
                         dtype=np.uint8).reshape(-1, 64).copy()
    msgs = [m for _, _, m in items]
    sigs[3, 40] ^= 0x10   # corrupt one S
    k, neg_a, ok = host_prepare(pubs, sigs, msgs)
    assert ok.all()

    def layout(a):
        return np.ascontiguousarray(
            a.astype(np.int32).T)
    s_d = layout(sigs[:, 32:])
    k_d = layout(k)
    nax_d = layout(neg_a[:, :32])
    nay_d = layout(neg_a[:, 32:])
    r_d = layout(sigs[:, :32])
    got = np.asarray(ep.verify_kernel_pallas(
        s_d, k_d, nax_d, nay_d, r_d, interpret=True, blk=8))
    want = [ref.verify(bytes(pubs[i]), bytes(sigs[i]), msgs[i])
            for i in range(8)]
    assert list(got) == want


# ------------------------------------------------------- device SHA-512 ----

class TestDeviceSha:
    """ops/sha512.py: on-device SHA-512 + exact mod-L vs hashlib / ints."""

    def test_sha512_96_vs_hashlib(self):
        from stellar_core_tpu.ops import sha512 as dsha
        rng = np.random.default_rng(11)
        r = rng.integers(0, 256, (17, 32)).astype(np.uint8)
        a = rng.integers(0, 256, (17, 32)).astype(np.uint8)
        m = rng.integers(0, 256, (17, 32)).astype(np.uint8)
        got = np.asarray(dsha.sha512_96(r, a, m))          # (64, B)
        for i in range(17):
            want = hashlib.sha512(
                bytes(r[i]) + bytes(a[i]) + bytes(m[i])).digest()
            assert bytes(got[:, i].astype(np.uint8)) == want, i

    def test_mod_l_random_and_adversarial(self):
        from stellar_core_tpu.ops import sha512 as dsha
        L = dsha.L
        rng = np.random.default_rng(12)
        vals = [int.from_bytes(rng.integers(0, 256, 64).astype(
            np.uint8).tobytes(), "little") for _ in range(24)]
        # adversarial: 0, 1, L-1, L, L+1, k*L near the top, all-0xFF,
        # max value, and values engineered to stress the fold carries
        vals += [0, 1, L - 1, L, L + 1, 2**512 - 1,
                 (2**512 // L) * L, (2**512 // L) * L - 1,
                 15 * L, 16 * L - 1, 2**256 - 1, 2**256, 2**269]
        arr = np.zeros((64, len(vals)), dtype=np.int32)
        for j, v in enumerate(vals):
            for i in range(64):
                arr[i, j] = (v >> (8 * i)) & 0xFF
        got = np.asarray(dsha.mod_l(arr))
        for j, v in enumerate(vals):
            want = v % L
            gv = int.from_bytes(
                bytes(got[:, j].astype(np.uint8)), "little")
            assert gv == want, (j, hex(v))

    def test_msg32_kernel_matches_hostk_and_oracle(self):
        """The message lengths alone pick the kernel: a batch of
        32-byte messages takes the device-SHA kernel, any other takes
        host k and the full kernel; both agree with the oracle on
        valid + corrupted batches."""
        v = TpuBatchVerifier()
        for msg_len, fn in ((32, v._jit_msg32), (33, v._jit)):
            items = _mk(12, msg_len=msg_len)
            assert all(len(m) == msg_len for _, _, m in items)
            # corrupt a few: bad sig byte, bad pubkey, bad msg
            p, s, m = items[3]
            items[3] = (p, s[:10] + bytes([s[10] ^ 1]) + s[11:], m)
            p, s, m = items[5]
            items[5] = (p[:0] + bytes([p[0] ^ 4]) + p[1:], s, m)
            p, s, m = items[7]
            items[7] = (p, s, bytes([m[0] ^ 0x80]) + m[1:])
            pubs = np.frombuffer(b"".join(p for p, _, _ in items), np.uint8)
            sigs = np.frombuffer(b"".join(s for _, s, _ in items), np.uint8)
            assert v._pack(pubs, sigs, [m for _, _, m in items]).fn is fn
            want = [ref.verify(pp, ss, mm) for pp, ss, mm in items]
            assert want.count(False) == 3
            assert v.verify_tuples(items) == want, msg_len

    def test_msg32_sharded_matches(self):
        """Device-SHA path through the sharded 8-device mesh verifier."""
        items = _mk(19, seed=3)
        v = ShardedBatchVerifier()
        got = v.verify_tuples(items)
        want = [ref.verify(p, s, m) for p, s, m in items]
        assert got == want
