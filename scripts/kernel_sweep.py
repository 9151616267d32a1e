"""On-chip kernel variant sweep: times the raw verify kernel (device
compute only, inputs pre-staged) across configuration variants.
Measurement tool behind docs/KERNEL_NOTES.md.

Usage: python scripts/kernel_sweep.py [batch ...]
Env: ED25519_SCAN_UNROLL is swept internally.
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import numpy as np
    import jax

    from stellar_core_tpu.util.jax_cache import enable_compile_cache
    enable_compile_cache()

    batches = [int(a) for a in sys.argv[1:]] or [16384]
    unrolls = [int(u) for u in
               os.environ.get("SWEEP_UNROLLS", "1,2,4").split(",")]

    from stellar_core_tpu.ops import ed25519_kernel as ek

    def staged(n):
        import hashlib
        from stellar_core_tpu.crypto import ed25519_ref as ref
        from stellar_core_tpu.crypto.keys import SecretKey
        pubs = np.zeros((n, 32), np.uint8)
        sigs = np.zeros((n, 64), np.uint8)
        ks = np.zeros((n, 32), np.uint8)
        sk = SecretKey.pseudo_random_for_testing(1)
        pub = sk.public_key().raw
        for i in range(n):
            m = hashlib.sha256(b"sweep%d" % i).digest()
            sig = sk.sign(m)
            pubs[i] = np.frombuffer(pub, np.uint8)
            sigs[i] = np.frombuffer(sig, np.uint8)
            kk = int.from_bytes(
                hashlib.sha512(sig[:32] + pub + m).digest(),
                "little") % ref.L
            ks[i] = np.frombuffer(kk.to_bytes(32, "little"), np.uint8)
        return pubs, sigs, ks

    for bsz in batches:
        pubs, sigs, ks = staged(min(bsz, 512))
        reps = -(-bsz // pubs.shape[0])
        a = np.tile(pubs, (reps, 1))[:bsz]
        full = np.tile(sigs, (reps, 1))[:bsz]
        r, s = full[:, :32], full[:, 32:]
        k = np.tile(ks, (reps, 1))[:bsz]
        for unroll in unrolls:
            ek.SCAN_UNROLL = unroll
            fn = jax.jit(ek.verify_kernel_full)
            da, dr, ds, dk = (jax.device_put(x) for x in (a, r, s, k))
            t0 = time.perf_counter()
            out = np.asarray(fn(da, dr, ds, dk))
            compile_s = time.perf_counter() - t0
            assert out.all(), "kernel rejected valid signatures!"
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                out = np.asarray(fn(da, dr, ds, dk))
                best = min(best, time.perf_counter() - t0)
            print(f"batch={bsz} unroll={unroll}: "
                  f"{bsz / best:,.0f}/s (best {best:.3f}s, "
                  f"first+compile {compile_s:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
