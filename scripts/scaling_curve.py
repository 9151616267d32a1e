"""Measure the data-parallel scaling curve of the sharded Ed25519
verifier on a virtual CPU mesh (VERDICT r04 next-step #3).

The production multi-chip story rests on "dp sharding is ~linear": the
per-shard program is identical on every device and the only cross-device
traffic is the (B,) bool result gather (ops/verifier.py:238-247). Real
multi-chip hardware is not available here, so this harness measures the
thing that IS measurable in simulation: **sharding overhead**. On a
host with one physical core, N virtual XLA:CPU devices execute their
shards (near-)sequentially, so perfect sharding predicts

    t_N(B)  ~=  N * t_1(B/N)

and any partition/collective/launch overhead shows up as
t_N(B) exceeding that. We record

    sharding_efficiency(N) = N * t_1(B/N) / t_N(B)

for N in {1,2,4,8} (best-of-3 each), plus the projected multi-chip
throughput = real-chip rate x N x efficiency, using the per-chip
absolute from the newest VERIFY_rNN.json (recorded on the real TPU).

Run under the CPU mesh env (the conftest's env, or):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/scaling_curve.py [--batch 8192] [--out SCALING.json]

Reference frame: SURVEY.md §5.7/§5.8 — dp is the production sharding;
the reference scales horizontally by adding validator processes, we
scale a single validator's verify stage by adding chips.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# the curve runs on the virtual CPU mesh whatever platform the
# environment names
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def _host_state() -> dict:
    import bench
    return bench._host_state()


def _make_batch(n: int):
    """Random valid signatures via the native signer (fast) with a few
    invalid lanes mixed in so the device actually computes rejections."""
    import hashlib

    from stellar_core_tpu.crypto import ed25519_ref as ref
    pubs = np.zeros((n, 32), dtype=np.uint8)
    sigs = np.zeros((n, 64), dtype=np.uint8)
    msgs = []
    n_keys = 16
    keyed = []
    for i in range(n_keys):
        seed = hashlib.sha256(b"scale-key-%d" % i).digest()
        keyed.append((seed, ref.secret_to_public(seed)))
    for i in range(n):
        seed, pub = keyed[i % n_keys]
        msg = hashlib.sha256(b"scale-msg-%d" % i).digest()
        msgs.append(msg)
        pubs[i] = np.frombuffer(pub, dtype=np.uint8)
        sigs[i] = np.frombuffer(ref.sign(seed, msg), dtype=np.uint8)
    # corrupt every 97th signature
    bad = np.arange(0, n, 97)
    sigs[bad, 0] ^= 0xFF
    expect = np.ones(n, dtype=bool)
    expect[bad] = False
    return pubs, sigs, msgs, expect


def _time_verify(v, pubs, sigs, msgs, expect, reps: int = 10) -> float:
    """Best-of-reps wall seconds for one full verify_batch call."""
    res = v.verify_batch(pubs, sigs, msgs)          # warmup + compile
    assert (res == expect).all(), "verifier wrong on warmup"
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        res = v.verify_batch(pubs, sigs, msgs)
        best = min(best, time.perf_counter() - t0)
    assert (res == expect).all()
    return best


def _newest_verify_artifact() -> dict:
    files = sorted(glob.glob(os.path.join(ROOT, "VERIFY_r*.json")),
                   key=lambda f: int(re.search(r"r(\d+)", f).group(1)))
    if not files:
        return {}
    with open(files[-1]) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    # XLA:CPU compile time of the sharded kernel grows super-linearly
    # with the shard shape (bucket-1024 measured >20 min on this host);
    # 256 keeps every shape in the suite-proven compile range
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from stellar_core_tpu.ops.verifier import ShardedBatchVerifier
    from stellar_core_tpu.util.jax_cache import enable_compile_cache
    enable_compile_cache()

    devices = jax.devices()
    if len(devices) < 8:
        raise SystemExit("need 8 virtual devices (set XLA_FLAGS before "
                         "any jax import)")
    B = args.batch
    host0 = _host_state()
    pubs, sigs, msgs, expect = _make_batch(B)

    # per-shard single-device times t_1(B/N) — the sequential ideal
    t1_of = {}
    for n_shard in [B, B // 2, B // 4, B // 8]:
        v1 = ShardedBatchVerifier(devices=devices[:1], device_sha=False)
        t1_of[n_shard] = _time_verify(
            v1, pubs[:n_shard], sigs[:n_shard], msgs[:n_shard],
            expect[:n_shard])
        print(f"t_1({n_shard}) = {t1_of[n_shard]*1e3:.1f} ms",
              file=sys.stderr, flush=True)

    rows = []
    for ndev in [1, 2, 4, 8]:
        v = ShardedBatchVerifier(devices=devices[:ndev], device_sha=False)
        t_n = _time_verify(v, pubs, sigs, msgs, expect)
        ideal = ndev * t1_of[B // ndev]
        eff = ideal / t_n
        rows.append({
            "ndev": ndev,
            "batch": B,
            "t_ms": round(t_n * 1e3, 1),
            "rate_cpu_mesh": round(B / t_n, 1),
            "t1_shard_ms": round(t1_of[B // ndev] * 1e3, 1),
            "sharding_efficiency": round(eff, 3),
        })
        print(f"ndev={ndev}: t={t_n*1e3:.1f} ms ideal={ideal*1e3:.1f} ms "
              f"efficiency={eff:.3f}", file=sys.stderr, flush=True)

    chip = _newest_verify_artifact()
    chip_rate = chip.get("value")
    projection = None
    if chip_rate:
        eff8 = rows[-1]["sharding_efficiency"]
        projection = {
            "per_chip_rate": chip_rate,
            "assumed_efficiency": eff8,
            "projected_rate_8chip": round(chip_rate * 8 * eff8, 1),
            "chips_to_10x_vs_baseline": None,
        }
        vsb = chip.get("vs_baseline")
        if vsb:
            import math
            projection["chips_to_10x_vs_baseline"] = \
                math.ceil(10.0 / (vsb * eff8))

    out = {
        "metric": "dp_sharding_scaling",
        "unit": "sharding_efficiency",
        "value": rows[-1]["sharding_efficiency"],
        "batch": B,
        "curve": rows,
        "real_chip": {"rate": chip_rate,
                      "vs_baseline": chip.get("vs_baseline")},
        "projection": projection,
        "host_load": {"start": host0, "end": _host_state()},
        "note": "1 physical core: efficiency isolates shard_map/collective "
                "overhead (t_N vs N*t_1(B/N)), not wall-clock speedup",
    }
    path = args.out or os.path.join(ROOT, "SCALING_r05.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps({"recorded": path,
                      "efficiency_at_8": rows[-1]["sharding_efficiency"]}))


if __name__ == "__main__":
    main()
