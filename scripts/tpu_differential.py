"""Real-hardware Ed25519 differential job:
run the valid/corrupted/non-canonical/small-order vector suite on the
ACTUAL TPU chip (not the forced-CPU pytest platform), and cross-check
chip results against the CPU-mesh lowering and the pure-Python oracle
on 10k+ random+adversarial signatures.

Usage:
  python scripts/tpu_differential.py run --out FILE [--n 10000]
                                         [--msg32-fill 1000]
      # verify the vectors on whatever JAX platform this process sees;
      # writes results as an .npz. --msg32-fill N also sends the
      # 32-byte-message subset through the on-device-SHA kernel,
      # repeated up to N tuples so that it dispatches at a bucket
      # another phase already compiled (1000 -> the 1024 of a
      # 1,000-transaction set); the chip children below ask for that
  python scripts/tpu_differential.py orchestrate [--n 10000]
      # spawn the chip run and the CPU-mesh run in separate processes,
      # one after the other, then assert chip == cpu-mesh == oracle
  python scripts/tpu_differential.py fast [--n 200]
      # chip vs oracle only, the whole adversarial tail at a small bucket

`orchestrate` and `fast` never import JAX themselves: a chip belongs to
one process, and a parent that had touched JAX would keep it from the
chip child. The children get the plain environment (no JAX_PLATFORMS:
JAX then takes the chip, and fails at start-up where there is none).

The multiply that ships on the chip (`fe8._mul_rolled`) is not the one
tier 1 exercises, and this job is the only run that puts mixed-length
messages through `verify_kernel_full` on a chip (the benchmark's cells
check the 32-byte kernel on every run). Consensus safety: XLA:TPU and
XLA:CPU are not guaranteed identical lowerings of the int32 pipeline —
this job is the proof they agree on this kernel, on this chip, for
every rejection class.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# random tuples ahead of the adversarial tail in the `fast` tier
FAST_N = 200
# what the chip children pass as --msg32-fill
MSG32_FILL = 1000


def _run(out_path: str, n: int, msg32_fill: int) -> None:
    import numpy as np
    import jax

    # persistent XLA compile cache, placed by util/jax_cache.py's rule
    from stellar_core_tpu.util.jax_cache import enable_compile_cache
    enable_compile_cache()

    from stellar_core_tpu.ops.testvectors import (make_differential_vectors,
                                                  oracle_results)
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier

    dev = jax.devices()[0]
    items = make_differential_vectors(n)
    want = oracle_results(items)
    v = TpuBatchVerifier()
    # mixed message lengths: host SHA-512, `verify_kernel_full`
    t0 = time.perf_counter()
    got = v.verify_tuples(items)
    dt = time.perf_counter() - t0
    # the 32-byte-message subset (the whole adversarial tail is in it)
    # through the tx-hash hot path: on-device SHA-512,
    # `verify_kernel_msg32` — the kernel a node's transactions take
    items32, want32 = [], []
    if msg32_fill > 0:
        idx32 = [i for i, it in enumerate(items) if len(it[2]) == 32]
        reps = max(1, msg32_fill // len(idx32))
        items32 = [items[i] for i in idx32] * reps
        want32 = [want[i] for i in idx32] * reps
    t0 = time.perf_counter()
    got32 = v.verify_tuples(items32)
    dt32 = time.perf_counter() - t0
    mism = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    mism32 = [i for i, (g, w) in enumerate(zip(got32, want32)) if g != w]
    np.savez(out_path,
             results=np.asarray(got, dtype=np.uint8),
             oracle=np.asarray(want, dtype=np.uint8))
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())},
                      "n": len(items), "n_msg32": len(items32),
                      "mismatches_vs_oracle": len(mism) + len(mism32),
                      "first_mismatches": mism[:10],
                      "first_mismatches_msg32": mism32[:10],
                      "secs": round(dt, 2), "secs_msg32": round(dt32, 2)}),
          flush=True)
    if mism or mism32:
        sys.exit(1)


def _chip_env() -> dict:
    """The plain environment for a chip child: JAX picks the platform."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    return env


def _orchestrate(n: int, out_dir: str) -> None:
    import numpy as np

    os.makedirs(out_dir, exist_ok=True)
    chip_out = os.path.join(out_dir, "chip.npz")
    cpu_out = os.path.join(out_dir, "cpu.npz")

    cpu_env = _chip_env()
    cpu_env["JAX_PLATFORMS"] = "cpu"
    cpu_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    for name, env, out, fill in (("chip", _chip_env(), chip_out, MSG32_FILL),
                                 ("cpu-mesh", cpu_env, cpu_out, 0)):
        print(f"[{name}] running differential suite ...", flush=True)
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "run",
             "--out", out, "--n", str(n), "--msg32-fill", str(fill)],
            env=env, cwd=REPO, timeout=3600)
        if r.returncode != 0:
            print(f"[{name}] FAILED against the oracle")
            sys.exit(1)

    chip = np.load(chip_out)["results"]
    cpu = np.load(cpu_out)["results"]
    if chip.shape != cpu.shape or not (chip == cpu).all():
        bad = int((chip != cpu).sum())
        print(f"CROSS-CHECK FAILED: chip and cpu-mesh disagree on "
              f"{bad} signatures")
        sys.exit(1)
    print(f"TPU DIFFERENTIAL: PASS ({len(chip)} signatures; "
          "chip == cpu-mesh == oracle)")


def _fast(n: int, out_dir: str) -> None:
    """Fast chip tier: the full strict-check corpus
    (non-canonical A/R/S, small order, torsion defects, mixed
    valid/invalid — the adversarial tail is appended whole regardless
    of n) at a small bucket, chip vs oracle only. Warm-cache target:
    <2 min wall. The chip==cpu-mesh cross-check stays in the full
    `orchestrate` tier."""
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "run",
         "--out", os.path.join(out_dir, "chip.npz"), "--n", str(n),
         "--msg32-fill", str(MSG32_FILL)],
        env=_chip_env(), cwd=REPO, timeout=1200)
    if r.returncode != 0:
        print("FAST DIFFERENTIAL: FAIL (chip vs oracle)")
        sys.exit(1)
    print(f"FAST DIFFERENTIAL: PASS in {time.perf_counter() - t0:.0f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["run", "orchestrate", "fast"])
    ap.add_argument("--out", default="tpu-diff.npz")
    ap.add_argument("--out-dir",
                    default=os.path.join(REPO, "chiprun_out", "tpu-diff"),
                    help="where orchestrate/fast keep their children's "
                         "results")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--msg32-fill", type=int, default=0)
    args = ap.parse_args()
    if args.mode == "run":
        _run(args.out, args.n if args.n is not None else 10000,
             args.msg32_fill)
    elif args.mode == "fast":
        _fast(args.n if args.n is not None else FAST_N, args.out_dir)
    else:
        _orchestrate(args.n if args.n is not None else 10000, args.out_dir)


if __name__ == "__main__":
    main()
