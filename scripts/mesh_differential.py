"""Four chips, one process: the sharded verifier against the
single-device one and the oracle.

    python scripts/mesh_differential.py --devices 4 [--n 200] [--batch 4096]

`SIGNATURE_VERIFY_MESH = "auto"` puts every node on a multi-chip host
on `ShardedBatchVerifier` (main/application.py `_make_batch_verifier`);
this is the one check of that path on real chips (`chiprun --chips 4
-- python scripts/mesh_differential.py --devices 4`). ONE process owns all the chips (a chip belongs to one process),
verifies the adversarial corpus (ops/testvectors.py, tpu_differential's
fast tier) and a valid batch with both verifiers, and checks:

  - identical per-signature results: sharded == single == oracle;
  - the output of the mesh program lives on `--devices` distinct
    devices (its shards);
  - a non-zero share on every `crypto.verify.dispatch.device<N>.batch`.

Prints one JSON object as its last line; exits 1 on any failed check.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--n", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4096)
    args = ap.parse_args()

    import hashlib

    import jax
    import numpy as np

    from stellar_core_tpu.util.jax_cache import enable_compile_cache
    enable_compile_cache()

    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.ops.testvectors import (make_differential_vectors,
                                                  oracle_results)
    from stellar_core_tpu.ops.verifier import (ShardedBatchVerifier,
                                               TpuBatchVerifier)
    from stellar_core_tpu.util.metrics import MetricsRegistry

    devices = jax.devices()
    problems = []
    if len(devices) != args.devices:
        problems.append(f"JAX sees {len(devices)} devices, wanted "
                        f"{args.devices}")
    metrics = MetricsRegistry()
    sharded = ShardedBatchVerifier(metrics=metrics)
    single = TpuBatchVerifier()

    corpus = make_differential_vectors(args.n)
    keys = [SecretKey.pseudo_random_for_testing(9000 + i) for i in range(32)]
    valid = []
    for i in range(args.batch):
        msg = hashlib.sha256(b"mesh-%d" % i).digest()
        sk = keys[i % len(keys)]
        valid.append((sk.public_key().raw, sk.sign(msg), msg))

    def differ(a, b) -> int:
        return sum(bool(x) != bool(y) for x, y in zip(a, b))

    report = {}
    for name, items, want in (("corpus", corpus, oracle_results(corpus)),
                              ("valid", valid, [True] * len(valid))):
        t0 = time.perf_counter()
        got_mesh = sharded.verify_tuples(items)
        t1 = time.perf_counter()
        got_one = single.verify_tuples(items)
        t2 = time.perf_counter()
        report[name] = {
            "n": len(items),
            "sharded_vs_oracle": differ(got_mesh, want),
            "single_vs_oracle": differ(got_one, want),
            "sharded_vs_single": differ(got_mesh, got_one),
            "first_call_secs": {"sharded": round(t1 - t0, 2),
                                "single": round(t2 - t1, 2)}}
        if any(report[name][k] for k in ("sharded_vs_oracle",
                                         "single_vs_oracle",
                                         "sharded_vs_single")):
            problems.append(f"{name}: results differ {report[name]}")

    # where the mesh program's output lives: one shard per device
    n = len(valid)
    pubs = np.frombuffer(b"".join(p for p, _, _ in valid),
                         dtype=np.uint8).reshape(n, 32)
    sigs = np.frombuffer(b"".join(s for _, s, _ in valid),
                         dtype=np.uint8).reshape(n, 64)
    msgs = np.frombuffer(b"".join(m for _, _, m in valid),
                         dtype=np.uint8).reshape(n, 32)
    fn, pin = sharded._program(sharded.active_indices(), True)
    out = fn(pubs, np.ascontiguousarray(sigs[:, :32]),
             np.ascontiguousarray(sigs[:, 32:]), msgs)
    shard_devices = sorted(str(s.device) for s in out.addressable_shards)
    if pin is not None or len(set(shard_devices)) != args.devices:
        problems.append(f"output shards on {shard_devices}, wanted "
                        f"{args.devices} distinct devices")
    if not bool(np.asarray(out).all()):
        problems.append("the mesh program rejected a valid signature")

    snap = metrics.to_json()
    shares = {}
    for i in range(len(devices)):
        h = snap.get("crypto.verify.dispatch.device%d.batch" % i) or {}
        shares[str(i)] = int(h.get("sum", 0))
        if not h.get("sum"):
            problems.append(f"device {i} verified nothing")

    print(json.dumps({
        "ok": not problems, "problems": problems,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "shard_devices": shard_devices,
        "per_device_signatures": shares, **report}), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
