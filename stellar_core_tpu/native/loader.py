"""Build + load the native C++ library (ctypes, no pybind11 dependency).

Generates SHA-512 round constants at build time from first principles
(fractional bits of cube/square roots of the first primes, FIPS 180-4 §4.2.3)
so no constant tables are copied from anywhere, then compiles
src/*.cpp -> build/libscnative-<digest>.so and exposes a typed ctypes
wrapper.

Nothing under build/ is committed, and a built file is named by a
digest of its sources, its flags and the machine it was built for
(`built_path`): the library is compiled with -march=native, so a file
another machine built — a copied tree, a shared disk — has another
name and is never opened, and a stale one neither.
"""

from __future__ import annotations

import ctypes
import hashlib
import math
import os
import platform
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_BUILD = os.path.join(_DIR, "build")
# SC_NATIVE_SANITIZE=1 selects a separate ASan+UBSan build (never
# clobbers the fast .so). Load it via scripts/native_sanitize.py, which
# LD_PRELOADs libasan — dlopen'ing an ASan DSO into a plain python
# otherwise trips ASan's link-order check. See docs/ANALYSIS.md.


def sanitize_enabled() -> bool:
    return os.environ.get("SC_NATIVE_SANITIZE", "") == "1"

_lock = threading.Lock()
_lib: Optional["NativeLib"] = None


def _primes(n: int) -> list:
    ps, c = [], 2
    while len(ps) < n:
        if all(c % p for p in ps):
            ps.append(c)
        c += 1
    return ps


def _icbrt(n: int) -> int:
    """Integer cube root via Newton iteration on Python ints."""
    if n == 0:
        return 0
    x = 1 << ((n.bit_length() + 2) // 3)
    while True:
        y = (2 * x + n // (x * x)) // 3
        if y >= x:
            return x
        x = y


def _gen_consts_header() -> str:
    # K_i = first 64 fractional bits of cbrt(prime_i); H0_i likewise for sqrt
    ks = []
    for p in _primes(80):
        frac = _icbrt(p << 192)  # floor(cbrt(p) * 2^64)
        ks.append(frac & ((1 << 64) - 1))
    hs = []
    for p in _primes(8):
        frac = math.isqrt(p << 128)  # floor(sqrt(p) * 2^64)
        hs.append(frac & ((1 << 64) - 1))
    lines = ["// generated by loader.py — do not edit", "#pragma once",
             "#include <cstdint>",
             "static const uint64_t SHA512_K[80] = {"]
    for i in range(0, 80, 4):
        lines.append("    " + ", ".join(f"0x{k:016x}ULL" for k in ks[i:i + 4]) + ",")
    lines.append("};")
    lines.append("static const uint64_t SHA512_H0[8] = {")
    for i in range(0, 8, 4):
        lines.append("    " + ", ".join(f"0x{h:016x}ULL" for h in hs[i:i + 4]) + ",")
    lines.append("};")
    return "\n".join(lines) + "\n"


def machine_id() -> str:
    """What a -march=native binary is tied to: the architecture and the
    first CPU's model and feature flags."""
    parts = [platform.system(), platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break                       # first CPU only
                if line.startswith(("model name", "flags", "Features")):
                    parts.append(line.strip())
    except OSError:
        parts.append(platform.processor())
    return "\n".join(parts)


def built_path(stem: str, sources: Sequence[bytes],
               flags: Sequence[str]) -> str:
    """build/<stem>-<digest>.so, the digest over the source texts, the
    compiler flags and `machine_id()`."""
    h = hashlib.sha256()
    for part in (*sources, " ".join(flags).encode(), machine_id().encode()):
        h.update(len(part).to_bytes(8, "little"))
        h.update(part)
    return os.path.join(_BUILD, f"{stem}-{h.hexdigest()[:16]}.so")


def compile_shared(so: str, cmd: List[str]) -> str:
    """Run `cmd + ["-o", <tmp>]` and move the result to `so` in one
    step: a concurrent builder (pytest-xdist workers share the build
    directory) or a killed compile never leaves a torn file under the
    final name."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n"
            f"{e.stderr.decode(errors='replace')}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def build(force: bool = False) -> str:
    san = sanitize_enabled()
    os.makedirs(_BUILD, exist_ok=True)
    srcs = sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC) if f.endswith(".cpp"))
    consts = _gen_consts_header()
    if san:
        # -O1 keeps stack traces honest; UBSan aborts on first report so
        # a signed-overflow/misaligned-load bug fails the test run loudly
        flags = ["-O1", "-g", "-fno-omit-frame-pointer",
                 "-fsanitize=address,undefined",
                 "-fno-sanitize-recover=undefined"]
    else:
        flags = ["-O3"]
    flags += ["-march=native", "-std=c++17", "-shared", "-fPIC", "-pthread"]
    texts = [consts.encode()]
    for path in srcs:
        with open(path, "rb") as f:
            texts.append(f.read())
    so = built_path("libscnative-san" if san else "libscnative", texts, flags)
    if not force and os.path.exists(so):
        return so
    # the generated header lives beside the binaries, not in src/
    hdr = os.path.join(_BUILD, "sha512_consts.h")
    tmp = f"{hdr}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(consts)
    os.replace(tmp, hdr)
    return compile_shared(so, ["g++"] + flags + [f"-I{_BUILD}"] + srcs)


class NativeLib:
    """Typed wrapper over the built libscnative."""

    def __init__(self, so_path: str):
        self._c = ctypes.CDLL(so_path)
        c = self._c
        c.sc_sha512.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                ctypes.c_char_p]
        c.sc_ed25519_verify.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                        ctypes.c_char_p, ctypes.c_size_t]
        c.sc_ed25519_verify.restype = ctypes.c_int
        u8p = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
        u64p = np.ctypeslib.ndpointer(dtype=np.uint64, flags="C_CONTIGUOUS")
        c.sc_ed25519_batch_verify.argtypes = [u8p, u8p, u8p, u64p,
                                              ctypes.c_uint64, u8p]
        c.sc_ed25519_batch_prepare.argtypes = [u8p, u8p, u8p, u64p,
                                               ctypes.c_uint64, u8p, u8p]
        c.sc_ed25519_batch_host_precheck.argtypes = [u8p, u8p,
                                                     ctypes.c_uint64, u8p, u8p]
        c.sc_ed25519_public_from_seed.argtypes = [ctypes.c_char_p,
                                                  ctypes.c_char_p]
        c.sc_ed25519_sign.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_char_p]
        self._selftest()

    def _selftest(self) -> None:
        # differential sanity vs hashlib on a few lengths
        for n in (0, 1, 63, 64, 111, 112, 127, 128, 300):
            data = bytes(range(256))[:n] * 1
            out = ctypes.create_string_buffer(64)
            self._c.sc_sha512(data, len(data), out)
            assert out.raw == hashlib.sha512(data).digest(), \
                f"native sha512 mismatch at len {n}"

    # -- API ----------------------------------------------------------------
    def sha512(self, data: bytes) -> bytes:
        out = ctypes.create_string_buffer(64)
        self._c.sc_sha512(data, len(data), out)
        return out.raw

    def verify(self, pub: bytes, sig: bytes, msg: bytes) -> bool:
        return bool(self._c.sc_ed25519_verify(pub, sig, msg, len(msg)))

    def public_from_seed(self, seed: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._c.sc_ed25519_public_from_seed(seed, out)
        return out.raw

    def sign(self, seed: bytes, pub: bytes, msg: bytes) -> bytes:
        """RFC 8032 detached signature (byte-identical to libsodium /
        ed25519_ref.sign); `pub` is the caller's cached public key."""
        out = ctypes.create_string_buffer(64)
        self._c.sc_ed25519_sign(seed, pub, msg, len(msg), out)
        return out.raw

    def batch_verify(self, pubs: np.ndarray, sigs: np.ndarray,
                     msgs: bytes, offsets: np.ndarray) -> np.ndarray:
        """CPU batch baseline. pubs: (n,32) u8, sigs: (n,64) u8,
        msgs concatenated with offsets: (n+1,) u64."""
        n = pubs.shape[0]
        results = np.zeros(n, dtype=np.uint8)
        msgs_arr = np.frombuffer(msgs, dtype=np.uint8)
        self._c.sc_ed25519_batch_verify(
            np.ascontiguousarray(pubs), np.ascontiguousarray(sigs),
            msgs_arr, np.ascontiguousarray(offsets), n, results)
        return results.astype(bool)

    def batch_prepare(self, pubs: np.ndarray, sigs: np.ndarray,
                      msgs: bytes, offsets: np.ndarray):
        """Host-side prep for the TPU kernel: k = SHA512(R‖A‖M) mod L per
        signature, plus S-canonicality flags."""
        n = pubs.shape[0]
        k_out = np.zeros((n, 32), dtype=np.uint8)
        s_ok = np.zeros(n, dtype=np.uint8)
        msgs_arr = np.frombuffer(msgs, dtype=np.uint8)
        self._c.sc_ed25519_batch_prepare(
            np.ascontiguousarray(pubs), np.ascontiguousarray(sigs),
            msgs_arr, np.ascontiguousarray(offsets), n, k_out, s_ok)
        return k_out, s_ok.astype(bool)

    def batch_host_precheck(self, pubs: np.ndarray, sigs: np.ndarray):
        """Strict decompress + small-order checks for A and R; returns
        (neg_a_xy (n,64) u8 — affine -A as canonical x‖y bytes, ok (n,) bool)."""
        n = pubs.shape[0]
        neg_a_xy = np.zeros((n, 64), dtype=np.uint8)
        ok = np.zeros(n, dtype=np.uint8)
        self._c.sc_ed25519_batch_host_precheck(
            np.ascontiguousarray(pubs), np.ascontiguousarray(sigs), n,
            neg_a_xy, ok)
        return neg_a_xy, ok.astype(bool)


def get_lib() -> NativeLib:
    global _lib
    with _lock:
        if _lib is None:
            _lib = NativeLib(build())
        return _lib
