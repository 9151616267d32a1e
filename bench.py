"""North-star benchmark: Ed25519 batch verify throughput, TPU vs CPU.

Prints ONE JSON line:
  {"metric": "ed25519_verify_throughput", "value": <tpu verifies/sec>,
   "unit": "verifies/sec", "vs_baseline": <tpu / cpu-single-core>}

Baseline = the native C++ strict verifier (same algorithm family as
libsodium's ref10; reference harness: crypto/SecretKey.cpp:192-232,
self-check phase 4 main/ApplicationUtils.cpp:501-505) measured on one CPU
core of this host. TPU number is the full end-to-end pipeline (host
SHA-512, uint8 transfer, on-device decompress + double scalar mult),
async-pipelined across batches.

`python bench.py --catchup [n_ledgers]` runs the second BASELINE.md
scenario instead: publish a synthetic history then replay it through
catchup twice — sync CPU verify vs the TPU batch-prevalidation path —
reporting ledgers/sec for both.

`python bench.py --tps` runs the third BASELINE.md scenario: standalone
loadgen PAY (reference: generateload on stellar-core_standalone.cfg,
performance-eval/performance-eval.md:71-79), completion-tracked
applied-transactions/sec.

`python bench.py --tps-multi` runs the BASELINE.md max-TPS multinode
scenario: a 3-node core quorum over loopback with real SCP consensus
(Simulation/Topologies + LoadGenerator), counting payments externalized
by every node.

The DEFAULT run records all side scenarios every round (VERDICT r02
next-step #4): catchup / TPS / multinode-TPS (loopback + TCP) results
land in CATCHUP_rNN.json / TPS_rNN.json / TPSM_rNN.json / TPSMT_rNN.json
next to this file (NN = current round, inferred from the newest
BENCH_rNN.json + 1), while stdout stays exactly ONE JSON line — the
verify metric the driver parses (its hygiene sidecar: VERIFY_rNN.json).
SC_BENCH_VERIFY_ONLY=1 skips the side scenarios.

Bench hygiene (VERDICT r04 next-step #2): every artifact carries
`samples` (per-window / per-replay rates; the recorded value is
best-of-N or min-wall), `host_load` {loadavg, ncpu, spin_ms} at start
and end, and a `host_busy` flag when the box looked contended.
"""

import json
import os
import sys
import time

import numpy as np


def _enable_compile_cache():
    """Persistent XLA compile cache under the one rule of
    util/jax_cache.py, so repeated bench runs skip the multi-minute
    kernel compile."""
    from stellar_core_tpu.util.jax_cache import enable_compile_cache
    enable_compile_cache()


def _bench_verify_backend(default: str = "tpu") -> str:
    """Verify backend for the multinode wire-path legs (TPSM/TPSMT).
    The full device stack is the default (ISSUE 4), but on a host
    whose XLA device path is degraded — cold compiles measured in
    minutes, steady-state device dispatch slower than the 2s collect
    deadline, so every leg measures breaker thrash instead of the
    overlay — `SC_BENCH_VERIFY_BACKEND=native` pins the reference
    C verify path so the WIRE path stays the measured variable. The
    choice is recorded in the artifact (`verify_backend`), and a
    head-control leg must use the same value to be comparable."""
    return os.environ.get("SC_BENCH_VERIFY_BACKEND", default)


def _device_verify_probe(bucket: int) -> dict:
    """Warm device verify throughput at `bucket` vs the native C path on
    the same junk batch — the health check the catchup legs consult
    before betting the pipeline on the device. On a host with a real
    chip the device wins by ~4x (VERIFY_r05); on a host whose XLA
    device path is degraded to the CPU interpreter the same kernel
    runs ~1000x slower than native, every batch starves the apply
    thread, and the leg measures the broken backend instead of the
    pipeline. The probe pays one compile (persistent-cached) plus one
    warm dispatch, and its verdict + both rates ride the artifact."""
    from stellar_core_tpu.native import loader
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    rng = np.random.default_rng(7)
    dummy = rng.integers(0, 256, size=(bucket, 96), dtype=np.uint8)
    msgs = [b"x" * 32] * bucket
    pubs = np.ascontiguousarray(dummy[:, :32])
    sigs = np.ascontiguousarray(dummy[:, 32:])
    v = TpuBatchVerifier()
    v.verify_batch(pubs, sigs, msgs)          # compile + warm
    t0 = time.perf_counter()
    v.verify_batch(pubs, sigs, msgs)
    dev_dt = time.perf_counter() - t0
    lib = loader.get_lib()
    offsets = np.arange(bucket + 1, dtype=np.uint64) * 32
    blob = b"".join(msgs)
    t0 = time.perf_counter()
    lib.batch_verify(pubs, sigs, blob, offsets)
    nat_dt = time.perf_counter() - t0
    device_rate = bucket / dev_dt if dev_dt > 0 else float("inf")
    native_rate = bucket / nat_dt if nat_dt > 0 else float("inf")
    return {"bucket": bucket,
            "device_sigs_per_sec": round(device_rate, 1),
            "native_sigs_per_sec": round(native_rate, 1),
            "degraded": device_rate < native_rate}


def _require_device(probe: dict) -> None:
    """A bench leg that asked for the device and did not get one
    raises; it does not carry on with `verify_backend: native`."""
    if probe["degraded"]:
        raise RuntimeError(
            "device probe: degraded (%.0f sigs/s device vs %.0f native) — "
            "this leg asked for the device; run it on the chip or ask for "
            "SC_BENCH_VERIFY_BACKEND=native" % (
                probe["device_sigs_per_sec"],
                probe["native_sigs_per_sec"]))


def _make_batch(n):
    import hashlib
    from stellar_core_tpu.native import loader
    lib = loader.get_lib()
    pubs = np.zeros((n, 32), dtype=np.uint8)
    sigs = np.zeros((n, 64), dtype=np.uint8)
    msgs = []
    rng = np.random.default_rng(1234)
    seeds = rng.integers(0, 256, size=(n, 32), dtype=np.int64).astype(np.uint8)
    # a handful of distinct signers reused cyclically keeps the one-time
    # pure-python signing setup cheap; every message is distinct
    from stellar_core_tpu.crypto import ed25519_ref as ref
    n_keys = 32
    keyed = []
    for i in range(n_keys):
        seed = bytes(seeds[i])
        keyed.append((seed, ref.secret_to_public(seed)))
    for i in range(n):
        seed, pub = keyed[i % n_keys]
        msg = hashlib.sha256(b"bench-%d" % i).digest()
        msgs.append(msg)
        pubs[i] = np.frombuffer(pub, dtype=np.uint8)
        sigs[i] = np.frombuffer(ref.sign(seed, msg), dtype=np.uint8)
    return pubs, sigs, msgs, lib


def _spin_ms() -> float:
    """Min-of-3 timing of a fixed arithmetic loop: a direct probe of how
    much of one core this process actually gets right now (loadavg lags
    and counts our own just-finished work)."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        s = 0
        for i in range(200_000):
            s += i * i
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return round(best, 2)


def _host_state() -> dict:
    """Host-load snapshot recorded into every artifact (VERDICT r04
    weak #1: single-sample numbers on a shared 1-core host swing ±70%;
    artifacts must carry enough state to judge contamination)."""
    la = os.getloadavg()
    return {
        "loadavg": [round(x, 2) for x in la],
        "ncpu": os.cpu_count(),
        "spin_ms": _spin_ms(),
    }


class _HostLoadWatch:
    """Continuous host-load sampling THROUGH the run (ISSUE 10
    satellite): start/end snapshots miss mid-run contention entirely —
    the CLUSTER_r09 75-107 tps spread was unattributable per leg. A
    daemon thread appends loadavg samples into a bounded TimeSeries
    ring every ``period_s``; ``stop()`` returns the min/mean/max
    envelope recorded into the artifact beside start/end."""

    def __init__(self, period_s: float = 1.0):
        import threading

        from stellar_core_tpu.util.timeseries import TimeSeries
        self.series = TimeSeries(capacity=4096)
        self._period = period_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.wait(self._period):
            self.series.append(
                {"t": time.monotonic(),
                 "load1": round(os.getloadavg()[0], 2)})

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=2.0)
        loads = [s["load1"] for s in self.series.samples()]
        if not loads:
            return {"samples": 0}
        return {"samples": len(loads),
                "min": min(loads),
                "mean": round(sum(loads) / len(loads), 2),
                "max": max(loads)}


def _with_host_state(result: dict, at_start: dict,
                     watch: "_HostLoadWatch" = None) -> dict:
    """Attach start/end host state + a busy flag. The flag is a loud
    marker, not an abort: the driver runs unattended, so a flagged
    artifact beats a missing one. With a `watch`, the continuous
    min/mean/max envelope lands beside the endpoints — shared-host
    noise becomes attributable per leg."""
    result["host_load"] = {"start": at_start, "end": _host_state()}
    if watch is not None:
        result["host_load"]["during"] = watch.stop()
    # host_busy gates the unattended trend regression check
    # (scripts/bench_trend.py): a contended box must not fail the
    # gate. Two ways the box's state can't be trusted: load was
    # actually high, OR the loadavg instrument itself is broken — a
    # multi-node bench ALWAYS drives load ≥ 1 for minutes, so a ring
    # of all-zero during-samples means /proc/loadavg is lying
    # (sandboxed kernels pin it at 0.00) and contention is UNKNOWABLE.
    # Unknown must gate like busy, not like idle.
    during = result["host_load"].get("during", {})
    instrument_dead = bool(during.get("samples", 0) >= 30
                           and during.get("max", 1.0) == 0.0)
    if instrument_dead:
        result["host_load"]["instrument"] = "broken-loadavg"
    result["host_busy"] = at_start["loadavg"][0] > 1.5 or instrument_dead
    return result


def _close_phase_report(apps) -> dict:
    """Aggregate the ledger.close.* perf zones across nodes, keeping
    the WORST max_ms per phase — the slow-execution profile the
    acceptance gate reads (no closeLedger stall > 2000 ms attributable
    to the completion segment)."""
    phases: dict = {}
    for a in apps:
        for name, st in a.perf.report().items():
            if not (name.startswith("ledger.close") or
                    name == "ledger.closeLedger"):
                continue
            cur = phases.get(name)
            if cur is None:
                phases[name] = dict(st)
            else:
                cur["count"] += st["count"]
                cur["total_ms"] = round(cur["total_ms"] + st["total_ms"], 3)
                cur["max_ms"] = max(cur["max_ms"], st["max_ms"])
                cur["mean_ms"] = round(
                    cur["total_ms"] / max(1, cur["count"]), 3)
    return phases


def _verify_service_report(apps) -> dict:
    """Aggregate crypto.verify_service.* metrics across nodes (ISSUE 4):
    batch occupancy p50/p99 + mean, queue-wait percentiles, flush-reason
    tallies and device fallbacks — recorded beside close_phases/tx_e2e
    so a TPS regression on the flood path is diagnosable from the
    artifact alone."""
    flushes = 0
    submitted = 0
    occ_weighted = 0.0
    occ_p50 = occ_p99 = 0.0
    qw_p50 = qw_p99 = 0.0
    reasons: dict = {}
    fallbacks = 0
    for a in apps:
        j = a.metrics.to_json()
        occ = j.get("crypto.verify_service.occupancy")
        if not occ or not occ.get("count"):
            continue
        flushes += occ["count"]
        occ_weighted += occ["mean"] * occ["count"]
        occ_p50 = max(occ_p50, occ["median"])
        occ_p99 = max(occ_p99, occ["99%"])
        qw = j.get("crypto.verify_service.queue-wait", {})
        qw_p50 = max(qw_p50, qw.get("median", 0.0))
        qw_p99 = max(qw_p99, qw.get("99%", 0.0))
        sub = j.get("crypto.verify_service.submitted", {})
        submitted += sub.get("count", 0)
        for name, doc in j.items():
            if name.startswith("crypto.verify_service.flush."):
                r = name.rsplit(".", 1)[1]
                reasons[r] = reasons.get(r, 0) + doc["count"]
        fb = j.get("crypto.verify_service.fallback", {})
        fallbacks += fb.get("count", 0)
    if not flushes:
        return {}
    return {
        "submitted": submitted,
        "flushes": flushes,
        "occupancy_mean": round(occ_weighted / flushes, 2),
        "occupancy_p50": occ_p50,
        "occupancy_p99": occ_p99,
        "queue_wait_p50_ms": round(qw_p50 * 1000, 3),
        "queue_wait_p99_ms": round(qw_p99 * 1000, 3),
        "flush_reasons": reasons,
        "fallbacks": fallbacks,
    }


def _tx_e2e_report(app) -> dict:
    """Submit→externalize latency percentiles from the submit node's
    `ledger.transaction.e2e` timer (ISSUE 3: reported beside
    close_phases so a TPS number carries its latency distribution)."""
    j = app.metrics.to_json().get("ledger.transaction.e2e")
    if not j or not j.get("count"):
        return {}
    return {"count": j["count"],
            "median_ms": round(j["median"] * 1000, 3),
            "p99_ms": round(j["99%"] * 1000, 3),
            "mean_ms": round(j["mean"] * 1000, 3)}


def _scenario_reports(apps):
    """(timeseries, slo) artifact sections for in-process nodes
    (ISSUE 10) — the shared builder in util/timeseries.py, so every
    artifact producer emits the same shape."""
    from stellar_core_tpu.util.timeseries import scenario_reports
    return scenario_reports(apps)


def _start_tracing(apps) -> None:
    for a in apps:
        a.flight_recorder.start()


def _flood_report(apps) -> dict:
    """Flood-propagation snapshot for the TPSM/TPSMT artifacts (mesh
    observatory / ROADMAP item 3): aggregate duplicate-delivery ratio
    plus per-peer byte/message/duplicate totals, and — since the
    ISSUE 12 wire-path overhaul — the single-flight demand totals,
    the serialize-once encode-cache efficiency, and the SCP-vs-tx
    split of the dedup verdicts."""
    from stellar_core_tpu.overlay.manager import (
        finalize_flood_evidence, merge_flood_evidence)
    unique = dup = 0
    bytes_sent = bytes_recv = 0
    per_peer = []
    demand: dict = {}
    encode: dict = {}
    by_kind: dict = {}
    for a in apps:
        prop = getattr(a, "propagation", None)
        if prop is not None:
            rep = prop.report()
            unique += rep["unique"]
            dup += rep["duplicates"]
        om = getattr(a, "overlay_manager", None)
        if om is None:
            continue
        merge_flood_evidence(demand, om.demand_report())
        merge_flood_evidence(encode, om.encode_report())
        merge_flood_evidence(by_kind, om.flood_kind_report())
        label = a.flight_recorder.label or "node"
        for p in om.get_authenticated_peers():
            bytes_sent += p.bytes_written
            bytes_recv += p.bytes_read
            per_peer.append({
                "node": label,
                "peer": p.peer_id.hex()[:8] if p.peer_id else "?",
                "bytes_sent": p.bytes_written,
                "bytes_received": p.bytes_read,
                "messages_sent": p.messages_written,
                "messages_received": p.messages_read,
                "duplicates": p.duplicate_messages,
            })
    finalize_flood_evidence(demand, encode)
    return {
        "unique": unique,
        "duplicates": dup,
        "duplicate_ratio": round(dup / max(1, unique), 4),
        "bytes_sent_total": bytes_sent,
        "bytes_received_total": bytes_recv,
        "per_peer_bytes": per_peer,
        "demand": demand,
        "encode": encode,
        "by_kind": by_kind,
    }


def _dump_trace(apps, name: str) -> None:
    """Merge every node's flight-recorder buffer into ONE Chrome
    trace-event file (util/tracemerge.py: clock-aligned process lanes,
    per-node async tracks, hash-keyed flood hops stitched into flow
    chains); summarize/diff with scripts/trace_report.py, including
    the --slots / --flood cluster views."""
    from stellar_core_tpu.util.tracemerge import merge_recorders
    doc = merge_recorders([a.flight_recorder for a in apps])
    for a in apps:
        if a.flight_recorder.active:
            a.flight_recorder.stop()
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, name)
    with open(path, "w") as f:
        json.dump(doc, f)
    print("wrote trace: %s (%d events)" % (path,
                                           len(doc["traceEvents"])),
          file=sys.stderr, flush=True)


def _round_number() -> int:
    """Current round = newest committed artifact round + 1, across ALL
    scenario families (BENCH alone went stale once per-PR scenario
    artifacts like APPLYPAR_r16 started carrying the round forward)."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = [int(m.group(1)) for f in glob.glob(os.path.join(
        here, "*_r*.json"))
        if (m := re.search(r"_r(\d+)\.json$", f))]
    return (max(rounds) + 1) if rounds else 1


def _record_scenario(result: dict, prefix: str) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "%s_r%02d.json" % (prefix, _round_number()))
    with open(path, "w") as f:
        json.dump(result, f)
        f.write("\n")
    print("recorded %s: %s" % (path, result), file=sys.stderr, flush=True)


def main():
    if os.environ.get("SC_BENCH_VERIFY_ONLY") != "1":
        # record the other two BASELINE scenarios first so a verify-leg
        # failure can't lose them
        try:
            _record_scenario(bench_catchup(), "CATCHUP")
        except Exception as e:   # record the failure rather than dying
            _record_scenario({"metric": "catchup_replay_throughput",
                              "error": repr(e)}, "CATCHUP")
        try:
            _record_scenario(bench_tps(), "TPS")
        except Exception as e:
            _record_scenario({"metric": "loadgen_pay_tps",
                              "error": repr(e)}, "TPS")
        try:
            _record_scenario(bench_tps_soroban(), "TPSS")
        except Exception as e:
            _record_scenario({"metric": "loadgen_soroban_tps",
                              "error": repr(e)}, "TPSS")
        try:
            _record_scenario(bench_tps_multinode(), "TPSM")
        except Exception as e:
            _record_scenario({"metric": "loadgen_pay_tps_multinode",
                              "error": repr(e)}, "TPSM")
        try:
            _record_scenario(bench_tps_multinode_tcp(), "TPSMT")
        except Exception as e:
            _record_scenario({"metric": "loadgen_pay_tps_multinode_tcp",
                              "error": repr(e)}, "TPSMT")
        try:
            _record_scenario(bench_chaos(), "CHAOS")
        except Exception as e:
            _record_scenario({"metric": "chaos_convergence",
                              "error": repr(e)}, "CHAOS")
        try:
            _record_scenario(bench_tps_cluster(), "CLUSTER")
        except Exception as e:
            _record_scenario({"metric": "loadgen_pay_tps_cluster",
                              "error": repr(e)}, "CLUSTER")
        try:
            _record_scenario(bench_surge(), "SURGE")
        except Exception as e:
            _record_scenario({"metric": "surge_close_p99_control",
                              "error": repr(e)}, "SURGE")
        try:
            # snapshot-consistent read tier under write load (ISSUE 17)
            _record_scenario(bench_read(), "READ")
        except Exception as e:
            _record_scenario({"metric": "query_read_qps",
                              "error": repr(e)}, "READ")
        try:
            # TPSM over a seeded million-account ledger (ISSUE 17)
            _record_scenario(bench_tps_bigstate(), "TPSM_BIGSTATE")
        except Exception as e:
            _record_scenario({"metric": "loadgen_pay_tps_multinode_bigstate",
                              "error": repr(e)}, "TPSM_BIGSTATE")
        try:
            # streaming catchup over the seeded million-account bucket
            # state (ISSUE 19)
            _record_scenario(bench_catchup_bigstate(),
                             "CATCHUP_BIGSTATE")
        except Exception as e:
            _record_scenario({"metric":
                              "catchup_replay_throughput_bigstate",
                              "error": repr(e)}, "CATCHUP_BIGSTATE")
        try:
            # wide-area survival scenario matrix (ISSUE 20): real
            # process meshes under partition/flap/slow-link/surge/
            # sick-device fault windows, typed per-cell verdicts
            _record_scenario(bench_matrix(), "MATRIX")
        except Exception as e:
            _record_scenario({"metric": "matrix_cells_pass_fraction",
                              "error": repr(e)}, "MATRIX")
        try:
            # per-device health mesh degradation A/B (ISSUE 13); on a
            # single-device host the raised error is recorded rather
            # than faked with a 1-device "mesh"
            _record_scenario(bench_mesh_degrade(), "MESH")
        except Exception as e:
            _record_scenario({"metric": "mesh_degrade_retention",
                              "error": repr(e)}, "MESH")
        try:
            # sparse sizes on purpose: every distinct bucket pays a
            # per-process trace/lower (plus a one-time XLA compile), so
            # the default round samples the curve at 3 buckets —
            # `bench.py --min-batch` runs the dense sweep on demand
            _record_scenario(
                bench_min_batch(sizes=(1, 4, 16, 64)), "VERIFYMB")
        except Exception as e:
            _record_scenario({"metric": "verify_min_batch_crossover",
                              "error": repr(e)}, "VERIFYMB")
    # 16384 amortizes the per-dispatch overhead while keeping compile
    # time sane. 32768 measured +6% on raw device compute
    # (scripts/kernel_sweep.py: 32.8k/s vs 30.9k/s) but END-TO-END flat
    # (host-side SHA-512 prep grows with the batch and eats the gain),
    # so the smaller, faster-compiling bucket stays the default.
    # Batches are pipelined (async dispatch) so host SHA-512 + transfer
    # of batch i+1 overlap device compute of batch i.
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    host0 = _host_state()
    watch = _HostLoadWatch()
    pubs, sigs, msgs, lib = _make_batch(n)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    blob = b"".join(msgs)

    # --- CPU baseline (single core, native C++ strict verify);
    # best of 3 to shrug off transient host load ---
    cpu_n = min(n, 2048)
    off_c = offsets[:cpu_n + 1]
    cpu_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res_cpu = lib.batch_verify(pubs[:cpu_n], sigs[:cpu_n],
                                   blob[:int(off_c[-1])], off_c)
        cpu_dt = min(cpu_dt, time.perf_counter() - t0)
        assert res_cpu.all()
    cpu_rate = cpu_n / cpu_dt

    # --- TPU pipeline (async, overlapped batches) ---
    _enable_compile_cache()
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    # host-side k prep: this harness's host core is otherwise idle, so
    # prep overlaps device compute for free (35.8k vs 31.4k measured);
    # the node default is device_sha=True because there the host core is
    # the apply bottleneck — see docs/KERNEL_PROFILE.md §5
    v = TpuBatchVerifier(device_sha=False)
    res = None
    for attempt in range(3):                 # remote compile can flake
        try:
            res = v.verify_batch(pubs, sigs, msgs)   # warmup + compile
            break
        except Exception:
            if attempt == 2:
                raise
            time.sleep(5)
    assert res.all()
    iters = 4
    tpu_dt = float("inf")
    tpu_samples = []
    for _ in range(3):                       # best of 3 pipelined sets
        t0 = time.perf_counter()
        handles = [v.verify_batch_async(pubs, sigs, msgs)
                   for _ in range(iters)]
        results = [h() for h in handles]
        dt = (time.perf_counter() - t0) / iters
        tpu_samples.append(round(n / dt, 1))
        tpu_dt = min(tpu_dt, dt)
        assert all(r.all() for r in results)
    tpu_rate = n / tpu_dt

    result = {
        "metric": "ed25519_verify_throughput",
        "value": round(tpu_rate, 1),
        "unit": "verifies/sec",
        "vs_baseline": round(tpu_rate / cpu_rate, 3),
    }
    # fast strict-check differential on the SAME chip the bench ran on
    # (VERDICT r04 #8: kept green in the bench run): the full
    # adversarial corpus at a small bucket, chip vs python oracle
    try:
        from stellar_core_tpu.ops.testvectors import (
            make_differential_vectors, oracle_results)
        items = make_differential_vectors(200)
        mism = sum(1 for g, w in zip(v.verify_tuples(items),
                                     oracle_results(items)) if g != w)
        fastdiff = {"n": len(items), "mismatches": mism,
                    "status": "PASS" if mism == 0 else "FAIL"}
    except Exception as e:
        fastdiff = {"status": "ERROR", "error": repr(e)}
    print("fast differential: %s" % fastdiff, file=sys.stderr, flush=True)
    # hygiene sidecar: samples + host-load state for the verify metric
    # (stdout stays the canonical 4-field line the driver parses)
    _record_scenario(_with_host_state(
        dict(result, samples=tpu_samples,
             cpu_baseline_rate=round(cpu_rate, 1),
             fast_differential=fastdiff), host0, watch), "VERIFY")
    if os.environ.get("SC_BENCH_VERIFY_ONLY") != "1":
        # perf-trajectory snapshot LAST — after the VERIFY artifact
        # just recorded above — so EVERY family this round produced,
        # VERIFY included, is part of the trajectory the regression
        # gate judges (scripts/bench_trend.py)
        try:
            _record_scenario(bench_trend(), "TREND")
        except Exception as e:
            _record_scenario({"metric": "bench_trend",
                              "error": repr(e)}, "TREND")
    print(json.dumps(result))
    if fastdiff.get("status") == "FAIL":
        # a chip that miscomputes the strict-check corpus must not
        # report a green bench run
        sys.exit(1)


def bench_catchup(n_ledgers: int = 4096,
                  payments_per_ledger: int = 10) -> dict:
    """Publish a synthetic archive of `n_ledgers` mixed-workload ledgers
    (payments + resting DEX offers + soroban upload txs — the op families
    the reference's pubnet-replay scenario exercises,
    performance-eval/performance-eval.md:62-69), then time catchup replay
    with the sync CPU verifier vs the TPU batch-prevalidation path.
    Replay includes the archived-results verification leg."""
    import shutil
    import tempfile

    from stellar_core_tpu.catchup.catchup_work import (CatchupConfiguration,
                                                       CatchupWork)
    from stellar_core_tpu.catchup.pipeline import StreamingCatchupWork
    from stellar_core_tpu.history.archive import (CHECKPOINT_FREQUENCY,
                                                   make_tmpdir_archive)
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.work.basic_work import State
    from stellar_core_tpu.xdr.transaction import (Operation, _OperationBody,
                                                  PaymentOp, OperationType)
    from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType

    if n_ledgers < CHECKPOINT_FREQUENCY:
        raise SystemExit(f"--catchup needs at least {CHECKPOINT_FREQUENCY} "
                         "ledgers (one published checkpoint)")
    _enable_compile_cache()
    root_dir = tempfile.mkdtemp(prefix="bench-catchup-")
    archive_root = root_dir + "/archive"
    archive = make_tmpdir_archive("bench", archive_root)
    cfg = get_test_config()
    cfg.HISTORY = {"bench": {"get": archive.get_cmd,
                             "put": archive.put_cmd}}
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    app = Application.create(clock, cfg)
    app.start()

    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.xdr.transaction import ManageSellOfferOp
    from stellar_core_tpu.xdr.ledger_entries import Price

    t_pub = time.perf_counter()
    lg = LoadGenerator(app)
    n_accounts = 48
    created = 0
    while created < n_accounts:
        created += lg.generate_accounts(min(100, n_accounts - created))
        app.manual_close()
        lg.sync_account_seqs()
    # trustlines + LOAD funding so DEX offers can rest AND cross
    lg.setup_dex()
    app.manual_close()
    load_asset = Asset.credit(LoadGenerator.LOAD_ASSET_CODE,
                              lg.root.account_id)
    for acct in lg.accounts:
        lg._sign_and_submit(lg.root, [Operation(
            sourceAccount=None, body=_OperationBody(
                OperationType.PAYMENT, PaymentOp(
                    destination=acct.muxed, asset=load_asset,
                    amount=10_000_0000000)))])
        if lg.root.seq % 4 == 0:    # queue caps chained root txs
            app.manual_close()
    app.manual_close()

    def offer_op(i):
        # two out of three rest (sell native for LOAD above water);
        # every third sells LOAD back aggressively enough to CROSS the
        # resting book through OfferExchange — the expensive DEX path
        if i % 3 == 2:
            return Operation(sourceAccount=None, body=_OperationBody(
                OperationType.MANAGE_SELL_OFFER, ManageSellOfferOp(
                    selling=load_asset,
                    buying=Asset(AssetType.ASSET_TYPE_NATIVE),
                    amount=5000, price=Price(n=100, d=150), offerID=0)))
        return Operation(sourceAccount=None, body=_OperationBody(
            OperationType.MANAGE_SELL_OFFER, ManageSellOfferOp(
                selling=Asset(AssetType.ASSET_TYPE_NATIVE),
                buying=load_asset, amount=10000,
                price=Price(n=100 + (i % 32), d=100), offerID=0)))

    # soroban side of the mix: the native SAC + a deployed wasm counter
    # (VERDICT r04 #7 — the measured loop exercises the VM and the SAC)
    sac_cid = lg.setup_sac()
    counter_cid = lg.setup_counter_contract()
    app.manual_close()
    lg.sync_account_seqs()

    lcl = app.ledger_manager.get_last_closed_ledger_num()
    tx_i = 0
    while lcl < n_ledgers:
        # mixed ledgers: ~70% payments, ~30% offers (reference loadgen
        # MIXED_CLASSIC), plus a rotating soroban tx every 4th ledger —
        # upload-wasm / SAC transfer / contract invoke (reference
        # SOROBAN mode, LoadGenerator.cpp:469-494)
        for i in range(payments_per_ledger):
            src = lg.accounts[tx_i % len(lg.accounts)]
            if (tx_i * 30) % 100 < 30:
                lg._sign_and_submit(src, [offer_op(tx_i)])
            else:
                dst = lg.accounts[(tx_i + 1) % len(lg.accounts)]
                lg._sign_and_submit(src, [lg._payment_op(dst, 1000)])
            tx_i += 1
        if lcl % 4 == 0:
            kind = (lcl // 4) % 3
            if kind == 0:
                lg.generate_soroban_uploads(1)
            elif kind == 1:
                lg.generate_sac_transfers(sac_cid, 1)
            else:
                lg.generate_counter_invokes(counter_cid, 1)
        app.manual_close()
        lcl = app.ledger_manager.get_last_closed_ledger_num()
    if lg.failed:
        raise RuntimeError(f"{lg.failed} publish-phase txs failed")
    print("published %d mixed ledgers (%d txs) in %.1fs" % (
        app.ledger_manager.get_last_closed_ledger_num(), lg.submitted,
        time.perf_counter() - t_pub), file=sys.stderr, flush=True)

    def source_hash_at(seq: int) -> bytes:
        row = app.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
            (seq,))
        return bytes(row[0])

    def replay_once(backend: str, streaming: bool = False):
        # a catching-up node has never seen these signatures: the
        # process-global verify cache warmed by the publish phase must
        # not leak into the timed region (the reference's catchup runs
        # in a fresh process; this bench shares one)
        from stellar_core_tpu.crypto.keys import clear_verify_cache
        clear_verify_cache()
        cfg2 = get_test_config()
        cfg2.NETWORK_PASSPHRASE = cfg.NETWORK_PASSPHRASE
        cfg2.SIGNATURE_VERIFY_BACKEND = backend
        # replay node publishes nothing: skip tx history tables exactly
        # like the reference's in-memory catchup (MODE_STORES_HISTORY_MISC)
        cfg2.MODE_STORES_HISTORY_MISC = False
        app2 = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg2)
        app2.start()
        from stellar_core_tpu.work import run_work_to_completion
        bv = None
        if backend == "tpu":
            # compile outside the timed region: checkpoint batches land in
            # the power-of-two bucket >= payments_per_ledger * 64
            from stellar_core_tpu.ops.verifier import (TpuBatchVerifier,
                                                       _bucket_size)
            bv = TpuBatchVerifier()
            bucket = _bucket_size(payments_per_ledger
                                  * CHECKPOINT_FREQUENCY)
            rng = np.random.default_rng(7)
            dummy = rng.integers(0, 256, size=(bucket, 96),
                                 dtype=np.uint8)
            bv.verify_batch(dummy[:, :32], dummy[:, 32:],
                            [b"x" * 32] * bucket)
        work_cls = StreamingCatchupWork if streaming else CatchupWork
        work = work_cls(app2, archive, CatchupConfiguration(to_ledger=0),
                        batch_verifier=bv)
        t0 = time.perf_counter()
        final = run_work_to_completion(app2, work)
        dt = time.perf_counter() - t0
        print("replay[%s%s]: %.1fs to ledger %d" % (
            backend, "/pipeline" if streaming else "",
            dt, app2.ledger_manager.get_last_closed_ledger_num()),
            file=sys.stderr, flush=True)
        assert final == State.WORK_SUCCESS, final
        n = app2.ledger_manager.get_last_closed_ledger_num()
        # catchup stops at the last PUBLISHED checkpoint boundary;
        # compare the replayed chain hash at exactly that ledger
        assert app2.ledger_manager.get_last_closed_ledger_hash() == \
            source_hash_at(n), "replayed chain diverged"
        evidence = None
        if streaming:
            # the ISSUE 19 acceptance evidence: stage occupancy/overlap
            # from the pipeline plus proof replay rode PR 16's staged
            # apply engine
            evidence = {
                "stages": work.stats.report(),
                "parallel_apply":
                    app2.ledger_manager.parallel_apply_report()}
        app2.shutdown()
        return n / dt, evidence

    # Device health gate: a leg that asked for the device and did not
    # get one (no chip; XLA:CPU at ~40 sigs/s vs ~10k native) raises —
    # it never pins the native verifier and records a number under the
    # device's name. The probe's rates ride the artifact.
    pipe_backend = _bench_verify_backend("tpu")
    probe = None
    if pipe_backend == "tpu":
        from stellar_core_tpu.ops.verifier import _bucket_size
        probe = _device_verify_probe(
            _bucket_size(payments_per_ledger * CHECKPOINT_FREQUENCY))
        _require_device(probe)

    # INTERLEAVED best-of-2 per leg: running the legs in blocks lets
    # slow box drift between blocks masquerade as a backend difference
    # (observed ±30% across a 10-minute bench run). The native leg is
    # the sequential reference path; the pipeline leg is the streaming
    # pipeline (the production CATCHUP_PIPELINE default).
    host0 = _host_state()
    watch = _HostLoadWatch()
    cpu_samples, pipe_samples, pipe_evidence = [], [], []
    for _ in range(2):
        rate, _ = replay_once("native")
        cpu_samples.append(round(rate, 1))
        rate, ev = replay_once(pipe_backend, streaming=True)
        pipe_samples.append(round(rate, 1))
        pipe_evidence.append(ev)
    cpu_rate = max(cpu_samples)
    pipe_rate = max(pipe_samples)
    best = pipe_evidence[pipe_samples.index(pipe_rate)]
    app.shutdown()
    shutil.rmtree(root_dir, ignore_errors=True)
    return _with_host_state({
        "metric": "catchup_replay_throughput",
        "value": round(pipe_rate, 1),
        "unit": "ledgers/sec",
        "vs_baseline": round(pipe_rate / cpu_rate, 3),
        "n_ledgers": n_ledgers,
        "samples": {"native": cpu_samples, "pipeline": pipe_samples},
        "verify_backend": pipe_backend,
        "device_probe": probe,
        "stages": best["stages"],
        "parallel_apply": best["parallel_apply"],
    }, host0, watch)


def bench_catchup_bigstate(n_accounts: int = 1_000_000,
                           n_ledgers: int = 256,
                           payments_per_ledger: int = 10) -> dict:
    """Streaming catchup over the ISSUE 17 million-account bucket
    state: seed the deep bucket-list levels of the publishing node,
    publish payment checkpoints on top (every 4th payment lands on a
    seeded account, so replay reads and rewrites entries behind the
    big levels), bucket-apply a fresh node to the FIRST checkpoint
    (untimed — that leg is ISSUE 17's fast-forward), then time the
    replay of the remaining checkpoints: sequential native CPU vs the
    streaming pipeline with device prevalidation."""
    import shutil
    import tempfile

    from stellar_core_tpu.catchup import (ApplyBucketsWork,
                                          CatchupConfiguration,
                                          CatchupWork,
                                          GetHistoryArchiveStateWork,
                                          StreamingCatchupWork)
    from stellar_core_tpu.history.archive import (CHECKPOINT_FREQUENCY,
                                                   make_tmpdir_archive)
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import (
        LoadGenerator, build_bigstate_buckets, bulk_account_id,
        install_bigstate_buckets)
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.work import run_work_to_completion
    from stellar_core_tpu.work.basic_work import State
    from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType
    from stellar_core_tpu.xdr.transaction import (MuxedAccount, Operation,
                                                  OperationType, PaymentOp,
                                                  _OperationBody)

    _enable_compile_cache()
    root_dir = tempfile.mkdtemp(prefix="bench-catchup-big-")
    archive = make_tmpdir_archive("bench", root_dir + "/archive")

    def big_cfg():
        cfg = get_test_config()
        # seeded ~23MB buckets must keep the INDIVIDUAL index (the
        # bench_read RANGE-page measurement)
        cfg.EXPERIMENTAL_BUCKETLIST_DB = True
        cfg.EXPERIMENTAL_BUCKETLIST_DB_INDEX_CUTOFF = 64
        return cfg

    cfg = big_cfg()
    cfg.HISTORY = {"bench": {"get": archive.get_cmd,
                             "put": archive.put_cmd}}
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()

    t_seed = time.perf_counter()
    hdr = app.ledger_manager.get_last_closed_ledger_header()
    seed_buckets = build_bigstate_buckets(n_accounts, hdr.ledgerVersion,
                                          hdr.ledgerSeq)
    install_bigstate_buckets(app, seed_buckets)
    app.manual_close()      # recompute bucketListHash over the levels
    print("seeded %d accounts in %.1fs" % (
        n_accounts, time.perf_counter() - t_seed), file=sys.stderr,
        flush=True)

    lg = LoadGenerator(app)
    n_lg = 32
    created = 0
    while created < n_lg:
        created += lg.generate_accounts(min(100, n_lg - created))
        app.manual_close()
        lg.sync_account_seqs()
    native = Asset(AssetType.ASSET_TYPE_NATIVE)
    t_pub = time.perf_counter()
    tx_i = 0
    lcl = app.ledger_manager.get_last_closed_ledger_num()
    while lcl < n_ledgers:
        for _ in range(payments_per_ledger):
            src = lg.accounts[tx_i % len(lg.accounts)]
            if tx_i % 4 == 0:
                # fund a seeded deep-level account: the replayed close
                # must read the entry out of the million-account levels
                # and write the update above them
                dest = MuxedAccount.from_ed25519(
                    bulk_account_id(tx_i % n_accounts))
                op = Operation(sourceAccount=None, body=_OperationBody(
                    OperationType.PAYMENT, PaymentOp(
                        destination=dest, asset=native, amount=1000)))
                lg._sign_and_submit(src, [op])
            else:
                dst = lg.accounts[(tx_i + 1) % len(lg.accounts)]
                lg._sign_and_submit(src, [lg._payment_op(dst, 1000)])
            tx_i += 1
        app.manual_close()
        lcl = app.ledger_manager.get_last_closed_ledger_num()
    if lg.failed:
        raise RuntimeError(f"{lg.failed} publish-phase txs failed")
    print("published %d bigstate ledgers (%d txs) in %.1fs" % (
        lcl, lg.submitted, time.perf_counter() - t_pub),
        file=sys.stderr, flush=True)

    first_cp = CHECKPOINT_FREQUENCY - 1

    def source_hash_at(seq: int) -> bytes:
        row = app.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
            (seq,))
        return bytes(row[0])

    def replay_once(backend: str, streaming: bool):
        from stellar_core_tpu.crypto.keys import clear_verify_cache
        clear_verify_cache()
        cfg2 = big_cfg()
        cfg2.NETWORK_PASSPHRASE = cfg.NETWORK_PASSPHRASE
        cfg2.SIGNATURE_VERIFY_BACKEND = backend
        cfg2.MODE_STORES_HISTORY_MISC = False
        app2 = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg2)
        # do NOT start (no genesis): the first-checkpoint state —
        # including the seeded million accounts — comes purely from
        # the archived buckets, outside the timed window
        has_work = GetHistoryArchiveStateWork(app2, archive,
                                              checkpoint=first_cp)
        final = run_work_to_completion(app2, has_work)
        assert final == State.WORK_SUCCESS, final
        ab = ApplyBucketsWork(app2, archive, has_work.has,
                              tempfile.mkdtemp(prefix="ab-"))
        final = run_work_to_completion(app2, ab)
        assert final == State.WORK_SUCCESS, final
        assert app2.ledger_manager.get_last_closed_ledger_num() == \
            first_cp
        bv = None
        if backend == "tpu":
            from stellar_core_tpu.ops.verifier import (TpuBatchVerifier,
                                                       _bucket_size)
            bv = TpuBatchVerifier()
            bucket = _bucket_size(payments_per_ledger
                                  * CHECKPOINT_FREQUENCY)
            rng = np.random.default_rng(7)
            dummy = rng.integers(0, 256, size=(bucket, 96),
                                 dtype=np.uint8)
            bv.verify_batch(dummy[:, :32], dummy[:, 32:],
                            [b"x" * 32] * bucket)
        work_cls = StreamingCatchupWork if streaming else CatchupWork
        work = work_cls(app2, archive, CatchupConfiguration(to_ledger=0),
                        batch_verifier=bv)
        t0 = time.perf_counter()
        final = run_work_to_completion(app2, work)
        dt = time.perf_counter() - t0
        assert final == State.WORK_SUCCESS, final
        n = app2.ledger_manager.get_last_closed_ledger_num()
        assert app2.ledger_manager.get_last_closed_ledger_hash() == \
            source_hash_at(n), "replayed chain diverged"
        replayed = n - first_cp
        print("bigstate replay[%s%s]: %d ledgers in %.1fs" % (
            backend, "/pipeline" if streaming else "", replayed, dt),
            file=sys.stderr, flush=True)
        evidence = None
        if streaming:
            evidence = {
                "stages": work.stats.report(),
                "parallel_apply":
                    app2.ledger_manager.parallel_apply_report()}
        app2.shutdown()
        return replayed / dt, evidence

    # same device health gate as bench_catchup
    pipe_backend = _bench_verify_backend("tpu")
    probe = None
    if pipe_backend == "tpu":
        from stellar_core_tpu.ops.verifier import _bucket_size
        probe = _device_verify_probe(
            _bucket_size(payments_per_ledger * CHECKPOINT_FREQUENCY))
        _require_device(probe)

    host0 = _host_state()
    watch = _HostLoadWatch()
    cpu_rate, _ = replay_once("native", streaming=False)
    pipe_rate, evidence = replay_once(pipe_backend, streaming=True)
    app.shutdown()
    shutil.rmtree(root_dir, ignore_errors=True)
    return _with_host_state({
        "metric": "catchup_replay_throughput_bigstate",
        "value": round(pipe_rate, 1),
        "unit": "ledgers/sec",
        "vs_baseline": round(pipe_rate / cpu_rate, 3),
        "accounts": n_accounts,
        "n_ledgers": n_ledgers,
        "samples": {"native": [round(cpu_rate, 1)],
                    "pipeline": [round(pipe_rate, 1)]},
        "verify_backend": pipe_backend,
        "device_probe": probe,
        "stages": evidence["stages"],
        "parallel_apply": evidence["parallel_apply"],
    }, host0, watch)


def bench_tps_multinode(n_nodes: int = 5, n_accounts: int = 1000,
                        txs_per_ledger: int = 1000,
                        n_ledgers: int = 7, n_windows: int = 3,
                        trace: bool = False,
                        seed_bigstate: int = 0) -> dict:
    """Max-TPS multinode scenario (BASELINE.md: `Simulation`/`Topologies`
    + LoadGenerator over loopback — src/simulation/Simulation.h:32-35):
    an n_nodes core quorum runs REAL SCP consensus over loopback peers;
    load lands on node 0 and floods; the measured rate counts payments
    externalized by EVERY node (slowest node's wall clock) — i.e. the
    full consensus + flood + apply pipeline, not a single-node close.
    vs_baseline = value / 200 as in the standalone scenario.

    Every node votes the max-tx-set-size upgrade at genesis (the
    reference loadgen does the same through `upgrades`, since the
    genesis header's maxTxSetSize of 100 would throttle the queue)."""
    from stellar_core_tpu.simulation import LoadGenerator, topologies

    # ISSUE 4: the multinode scenario runs the full device stack on
    # every node — batch verifier + coalescing verify service — so the
    # flood-admission and SCP-envelope hot paths coalesce into device
    # micro-batches (occupancy/queue-wait land in the artifact)
    _enable_compile_cache()

    def cfg_gen(cfg):
        cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
        cfg.SIGNATURE_VERIFY_BACKEND = _bench_verify_backend()
        # telemetry on the sim's VirtualClock (ISSUE 10): the TPSM
        # artifact carries a bounded series summary + SLO verdicts
        cfg.TELEMETRY_SAMPLE_PERIOD = 1.0
        if seed_bigstate:
            # seeded ~23MB buckets must keep the INDIVIDUAL index
            # (RANGE page scans measured 9.5ms/probe — see bench_read)
            cfg.EXPERIMENTAL_BUCKETLIST_DB = True
            cfg.EXPERIMENTAL_BUCKETLIST_DB_INDEX_CUTOFF = 64

    sim = topologies.core(n_nodes, configure=cfg_gen)

    def crank_to(target, timeout):
        # side-effecting progress calls stay out of `assert` so the
        # scenario cannot silently degrade under python -O
        if not sim.crank_until(lambda: sim.have_all_externalized(target),
                               timeout_virtual_seconds=timeout):
            raise RuntimeError(f"quorum stalled before ledger {target}")

    try:
        sim.start_all_nodes()
        crank_to(2, 120)
        app = sim.apps()[0]
        seed_s = 0.0
        if seed_bigstate:
            from stellar_core_tpu.simulation.load_generator import (
                build_bigstate_buckets, bulk_account_id,
                install_bigstate_buckets)
            # every node must seed at the SAME lcl: a node that closes
            # another ledger before installing would hash a different
            # bucket list and diverge the chain
            crank_to(max(a.ledger_manager.get_last_closed_ledger_num()
                         for a in sim.apps()), 120)
            lcls = {a.ledger_manager.get_last_closed_ledger_num()
                    for a in sim.apps()}
            if len(lcls) != 1:
                raise RuntimeError(f"nodes unaligned before seeding: {lcls}")
            hdr = app.ledger_manager.get_last_closed_ledger_header()
            t_seed = time.perf_counter()
            seed_buckets = build_bigstate_buckets(
                seed_bigstate, hdr.ledgerVersion, hdr.ledgerSeq)
            # ONE build, shared immutable Bucket objects on every node:
            # entry memory and the lazy per-bucket indexes are paid
            # once, and identical buckets keep bucketListHash agreeing
            for a in sim.apps():
                install_bigstate_buckets(a, seed_buckets)
            # pre-build the shared indexes outside the measured window
            app.query_service.query_accounts(
                [bulk_account_id(i) for i in
                 (0, seed_bigstate // 4, seed_bigstate // 2,
                  (3 * seed_bigstate) // 4)],
                deadline_ms=600_000)
            seed_s = time.perf_counter() - t_seed
        lg = LoadGenerator(app)
        created = 0
        while created < n_accounts:
            # root can chain pending-depth create-batches per ledger
            created += lg.generate_accounts(min(400, n_accounts - created))
            crank_to(app.ledger_manager.get_last_closed_ledger_num() + 2,
                     120)
            lg.sync_account_seqs()
        # clean per-phase close stats over the measured window only
        for a in sim.apps():
            a.perf.reset()
        if trace:
            _start_tracing(sim.apps())
        host0 = _host_state()
        watch = _HostLoadWatch()
        samples = []
        applied_total = 0
        dt_total = 0.0
        for _ in range(n_windows):
            applied = 0
            t0 = time.perf_counter()
            for _ in range(n_ledgers):
                applied += lg.generate_payments(txs_per_ledger)
                # all payments sit in node 0's queue before the trigger
                # fires, so one close per batch carries the whole load
                crank_to(app.ledger_manager.get_last_closed_ledger_num()
                         + 1, 180)
                lg.sync_account_seqs()
            dt = time.perf_counter() - t0
            samples.append(round(applied / dt, 1))
            applied_total += applied
            dt_total += dt
        if trace:
            _dump_trace(sim.apps(), "trace_tpsm.json")
        if lg.failed:
            raise RuntimeError(f"{lg.failed} loadgen txs failed")
        seq = min(a.ledger_manager.get_last_closed_ledger_num()
                  for a in sim.apps())
        if not sim.ledger_hashes_agree(seq):
            raise RuntimeError("nodes diverged under load")
        # value = SUSTAINED rate over all measured ledgers (>=20 per
        # VERDICT r04 #6); per-window samples expose load noise
        rate = applied_total / dt_total
        print("multinode loadgen: %d payments, %d nodes, %d ledgers "
              "in %.1fs, windows %s" %
              (applied_total, n_nodes, n_windows * n_ledgers, dt_total,
               samples), file=sys.stderr, flush=True)
        extra = {}
        if seed_bigstate:
            import random as _random
            # exercise the read path over the seeded levels (bloom
            # probes + index hits land in the bucket.index.* meters),
            # then drain every node's meters into the artifact
            rng = _random.Random(7)
            read_found = 0
            for _ in range(8):
                res = app.query_service.query_accounts(
                    [bulk_account_id(rng.randrange(seed_bigstate))
                     for _ in range(64)], deadline_ms=60_000)
                read_found += sum(1 for e in res.get("entries_xdr") or []
                                  if e is not None)
            bi = {"lookups": 0, "hit": 0, "miss": 0, "bloom_fp": 0}
            for a in sim.apps():
                rep = a.bucket_manager.drain_index_meters(
                    a.metrics,
                    extra_buckets=a.snapshots.live_buckets())
                for k in bi:
                    bi[k] += rep[k]
            extra = {"accounts": seed_bigstate,
                     "seed_s": round(seed_s, 1),
                     "seeded_reads_found": read_found,
                     "bucket_index": bi}
        timeseries, slo = _scenario_reports(sim.apps())
        return _with_host_state({
            "metric": ("loadgen_pay_tps_multinode_bigstate"
                       if seed_bigstate else "loadgen_pay_tps_multinode"),
            **extra,
            "value": round(rate, 1),
            "unit": "txs/sec",
            "vs_baseline": round(rate / 200.0, 3),
            "verify_backend": _bench_verify_backend(),
            "samples": samples,
            "best_window": max(samples),
            "n_ledgers_measured": n_windows * n_ledgers,
            # per-phase closeLedger breakdown over the measured window
            # (worst node): a stall now names the guilty phase instead
            # of one opaque closeLedger number
            "close_phases": _close_phase_report(sim.apps()),
            # submit→externalize latency on the submitting node
            "tx_e2e": _tx_e2e_report(app),
            # coalescing verify service: occupancy/queue-wait/fallbacks
            "verify_service": _verify_service_report(sim.apps()),
            # flood duplicate ratio + per-peer bytes (mesh observatory:
            # the redundancy baseline for the pull-mode flooding PR)
            "flood": _flood_report(sim.apps()),
            # bounded time-series summary + SLO verdicts (ISSUE 10):
            # the run's time dimension, linted by check_artifacts
            "timeseries": timeseries,
            "slo": slo,
        }, host0, watch)
    finally:
        sim.stop_all_nodes()


def bench_tps_bigstate(n_nodes: int = 3, n_accounts: int = 200,
                       txs_per_ledger: int = 400, n_ledgers: int = 5,
                       n_windows: int = 2) -> dict:
    """TPSM re-run over a seeded million-account bucket list (ISSUE
    17): the same real-SCP loopback quorum, but every node's deep
    bucket levels carry 10^6 synthetic accounts installed directly
    into the list (no per-tx close loop), so ledger close, flood and
    the read path all run over big state. The artifact carries the
    bucket.index hit/miss/bloom-fp evidence beside the TPS number.

    Smaller quorum + window than the plain TPSM round: the seeded
    buckets cost ~1.6GB to build and ~92MB/node to adopt into the
    bucket dirs, and the scenario's question is 'does big state bend
    the close path', not 'how wide is the quorum'."""
    return bench_tps_multinode(
        n_nodes=n_nodes, n_accounts=n_accounts,
        txs_per_ledger=txs_per_ledger, n_ledgers=n_ledgers,
        n_windows=n_windows, seed_bigstate=1_000_000)


def bench_tps_multinode_tcp(n_nodes: int = 5, n_accounts: int = 1000,
                            txs_per_ledger: int = 500,
                            n_ledgers: int = 7, n_windows: int = 3,
                            base_port: int = 37100,
                            trace: bool = False) -> dict:
    """TCP-mode variant of the multinode scenario (VERDICT r04 #6;
    reference: Simulation OVER_TCP, src/simulation/Simulation.h:32-35):
    the same n-node core quorum, but every peer link is a real
    authenticated localhost TCP socket and the clock runs in REAL_TIME
    (sockets cannot ride virtual time). Loadgen lands on node 0, floods
    over the wire, and the rate counts payments externalized by every
    node, hash-agreement checked."""
    import time as _time

    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.crypto.sha import sha256 as _sha
    from stellar_core_tpu.main import (Application, Config,
                                       QuorumSetConfig)
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    _enable_compile_cache()
    clock = VirtualClock(ClockMode.REAL_TIME)
    seeds = [SecretKey.from_seed(_sha(b"bench-tcp-%d" % i))
             for i in range(n_nodes)]
    node_ids = [s.public_key().raw for s in seeds]
    threshold = (2 * n_nodes + 2) // 3
    apps = []
    for i in range(n_nodes):
        cfg = Config()
        cfg.NETWORK_PASSPHRASE = "bench tcp multinode"
        cfg.NODE_SEED = seeds[i]
        cfg.NODE_IS_VALIDATOR = True
        cfg.RUN_STANDALONE = False
        cfg.FORCE_SCP = True
        cfg.MANUAL_CLOSE = False
        cfg.EXPECTED_LEDGER_CLOSE_TIME = 0.3
        cfg.ALLOW_LOCALHOST_FOR_TESTING = True
        cfg.PEER_PORT = base_port + i
        cfg.KNOWN_PEERS = [f"127.0.0.1:{base_port + j}"
                           for j in range(i)]
        cfg.QUORUM_SET = QuorumSetConfig(threshold=threshold,
                                         validators=list(node_ids))
        cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
        # full device stack on every node (ISSUE 4): the TCP-path
        # regression (TPSMT at 0.745×) is the flood-admission hot path
        # this service targets — occupancy lands in the artifact
        cfg.SIGNATURE_VERIFY_BACKEND = _bench_verify_backend()
        # controller manual-tick (ISSUE 12): every committed TPSMT
        # round predates the adaptive control plane (r11) — with it
        # live, a host whose closes run near the SLO measures the
        # shed ladder (90%+ of offered load rejected), not the wire
        # path this leg exists to compare across rounds
        cfg.CONTROLLER_TICK_PERIOD = 0
        apps.append(Application.create(clock, cfg))

    def crank_to(target: int, timeout_s: float) -> None:
        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            clock.crank(True)
            if all(a.ledger_manager.get_last_closed_ledger_num() >=
                   target for a in apps):
                return
        raise RuntimeError(f"TCP quorum stalled before ledger {target}")

    try:
        for a in apps:
            a.start()
        crank_to(2, 60)
        app = apps[0]
        lg = LoadGenerator(app)
        created = 0
        while created < n_accounts:
            created += lg.generate_accounts(min(400,
                                                n_accounts - created))
            crank_to(app.ledger_manager.get_last_closed_ledger_num() + 2,
                     60)
            lg.sync_account_seqs()
        for a in apps:
            a.perf.reset()
        if trace:
            _start_tracing(apps)
        host0 = _host_state()
        watch = _HostLoadWatch()
        samples = []
        applied_total = 0
        dt_total = 0.0
        for _ in range(n_windows):
            applied = 0
            t0 = time.perf_counter()
            for _ in range(n_ledgers):
                applied += lg.generate_payments(txs_per_ledger)
                crank_to(app.ledger_manager.get_last_closed_ledger_num()
                         + 1, 90)
                lg.sync_account_seqs()
            dt = time.perf_counter() - t0
            samples.append(round(applied / dt, 1))
            applied_total += applied
            dt_total += dt
        if trace:
            _dump_trace(apps, "trace_tpsmt.json")
        if lg.failed and not applied_total:
            raise RuntimeError(f"{lg.failed} loadgen txs failed")
        if lg.failed:
            # since the adaptive control plane (ISSUE 11), a node at
            # its SLO edge deliberately answers TRY_AGAIN_LATER —
            # rejected submissions under overload are a MEASUREMENT
            # (recorded below), not a harness failure; the rate counts
            # what was actually admitted and externalized. Voiding the
            # whole leg on any shed made TPSMT unrecordable on exactly
            # the hosts where the shed gate engages.
            print(f"tcp multinode loadgen: {lg.failed} submissions "
                  "rejected (shed/overload) — recorded in artifact",
                  file=sys.stderr, flush=True)
        seq = min(a.ledger_manager.get_last_closed_ledger_num()
                  for a in apps)
        hashes = {bytes(a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
            (seq,))[0]) for a in apps}
        if len(hashes) != 1:
            raise RuntimeError("TCP nodes diverged under load")
        rate = applied_total / dt_total
        print("tcp multinode loadgen: %d payments, %d nodes, %d ledgers "
              "in %.1fs, windows %s" %
              (applied_total, n_nodes, n_windows * n_ledgers, dt_total,
               samples), file=sys.stderr, flush=True)
        timeseries, slo = _scenario_reports(apps)
        return _with_host_state({
            "metric": "loadgen_pay_tps_multinode_tcp",
            "value": round(rate, 1),
            "unit": "txs/sec",
            "vs_baseline": round(rate / 200.0, 3),
            "verify_backend": _bench_verify_backend(),
            "samples": samples,
            "best_window": max(samples),
            "n_ledgers_measured": n_windows * n_ledgers,
            # submissions the nodes rejected (adaptive shed / queue
            # limits): offered = applied + failed
            "loadgen_failed": lg.failed,
            "close_phases": _close_phase_report(apps),
            "tx_e2e": _tx_e2e_report(app),
            "verify_service": _verify_service_report(apps),
            # real-wire flood redundancy + per-peer bytes: ROADMAP
            # item 3's success counters for TPSMT ≥ 1.0×
            "flood": _flood_report(apps),
            # REAL_TIME clock here, so the 1 Hz default sampler ran on
            # the wall clock — the `run`-mode telemetry path measured
            # in-process (ISSUE 10)
            "timeseries": timeseries,
            "slo": slo,
        }, host0, watch)
    finally:
        for a in apps:
            a.shutdown()


def bench_tps_soroban(n_accounts: int = 200, txs_per_ledger: int = 100,
                      n_ledgers: int = 5, n_windows: int = 2) -> dict:
    """SOROBAN-mode TPS (VERDICT r04 #7; reference: LoadGenerator
    SOROBAN modes, LoadGenerator.cpp:469-494): a standalone manual-close
    node applying InvokeHostFunction ledgers — half native-SAC
    transfers, half wasm counter invokes — completion-tracked
    applied-tx/s through the real host + VM + SAC."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    lg = LoadGenerator(app)
    created = 0
    while created < n_accounts:
        created += lg.generate_accounts(min(200, n_accounts - created))
        app.manual_close()
        lg.sync_account_seqs()
    sac_cid = lg.setup_sac()
    counter_cid = lg.setup_counter_contract()
    app.manual_close()
    lg.sync_account_seqs()

    host0 = _host_state()
    watch = _HostLoadWatch()
    samples = []
    applied_total = 0
    dt_total = 0.0
    for _ in range(n_windows):
        applied = 0
        t0 = time.perf_counter()
        for _ in range(n_ledgers):
            before = app.ledger_manager.get_last_closed_ledger_num()
            applied += lg.generate_sac_transfers(sac_cid,
                                                 txs_per_ledger // 2)
            applied += lg.generate_counter_invokes(counter_cid,
                                                   txs_per_ledger // 2)
            app.manual_close()
            assert app.ledger_manager.get_last_closed_ledger_num() == \
                before + 1
            lg.sync_account_seqs()
            app.telemetry.sample_now()   # one sample per closed ledger
        dt = time.perf_counter() - t0
        samples.append(round(applied / dt, 1))
        applied_total += applied
        dt_total += dt
    assert lg.failed == 0, lg.failed
    timeseries, slo = _scenario_reports([app])
    app.shutdown()
    rate = max(samples)
    print("soroban loadgen: %d invokes in %.1fs, windows %s" % (
        applied_total, dt_total, samples), file=sys.stderr, flush=True)
    return _with_host_state({
        "metric": "loadgen_soroban_tps",
        "value": rate,
        "unit": "txs/sec",
        "vs_baseline": round(rate / 200.0, 3),
        "samples": samples,
        "sustained": round(applied_total / dt_total, 1),
        "timeseries": timeseries,
        "slo": slo,
    }, host0, watch)


def bench_min_batch(sizes=(1, 2, 4, 8, 16, 32, 64, 128, 256),
                    iters: int = 30) -> dict:
    """A/B for the VERIFY_DEVICE_MIN_BATCH knob (ISSUE 4 satellite):
    native per-signature verify vs device dispatch at small batch
    sizes, over the 32-byte-message hot path the verify service feeds.
    The crossover — the smallest batch where the device wins — is what
    the config default should sit near on this host."""
    import hashlib

    from stellar_core_tpu.crypto import ed25519_ref as ref
    from stellar_core_tpu.crypto.keys import verify_sig_uncached
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier

    _enable_compile_cache()
    host0 = _host_state()
    watch = _HostLoadWatch()
    n_max = max(sizes)
    rng = np.random.default_rng(99)
    seeds = rng.integers(0, 256, size=(8, 32), dtype=np.int64
                         ).astype(np.uint8)
    keyed = [(bytes(s), ref.secret_to_public(bytes(s))) for s in seeds]
    items = []
    for i in range(n_max):
        seed, pub = keyed[i % len(keyed)]
        msg = hashlib.sha256(b"minbatch-%d" % i).digest()
        items.append((pub, ref.sign(seed, msg), msg))

    v = TpuBatchVerifier(device_min_batch=1)   # never bypass: raw device
    table = {}
    crossover = None
    for n in sizes:
        batch = items[:n]
        assert all(v.verify_tuples(batch))     # warm/compile the bucket
        dev_dt = nat_dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                v.verify_tuples(batch)
            dev_dt = min(dev_dt, (time.perf_counter() - t0) / iters)
            t0 = time.perf_counter()
            for _ in range(iters):
                for p, s, m in batch:
                    verify_sig_uncached(p, s, m)
            nat_dt = min(nat_dt, (time.perf_counter() - t0) / iters)
        table[str(n)] = {"device_us": round(dev_dt * 1e6, 1),
                         "native_us": round(nat_dt * 1e6, 1),
                         "device_wins": dev_dt < nat_dt}
        if crossover is None and dev_dt < nat_dt:
            crossover = n
        print("min-batch %4d: device %8.1fus native %8.1fus" %
              (n, dev_dt * 1e6, nat_dt * 1e6), file=sys.stderr,
              flush=True)
    return _with_host_state({
        "metric": "verify_min_batch_crossover",
        "value": float(crossover if crossover is not None else -1),
        "unit": "signatures",
        "vs_baseline": 1.0,
        "sizes": table,
    }, host0, watch)


def _force_virtual_devices(n: int = 8) -> None:
    """N-virtual-device CPU mesh for the functional mesh legs. Must run
    before the first jax import (mirrors scripts/scaling_curve.py) — a
    no-op when the flag is already set or real devices exist."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % n).strip()


def bench_mesh_degrade(batch: int = None, flushes: int = 4,
                       sick: int = None, seed: int = 13) -> dict:
    """Mesh degradation A/B (ISSUE 13 tentpole): fault ONE device of
    the sharded verify mesh mid-run and measure graceful capacity
    degradation instead of the old whole-backend trip to native.

    Three timed phases over the same signature batch through the
    supervised sharded verifier (ops/verifier.py ShardedBatchVerifier
    under ops/backend_supervisor.py per-device breakers):

    - **healthy**: full N-device mesh;
    - **degraded**: a device-index-matched chaos ``io_error`` window on
      the ``ops.backend.dispatch.device`` seam trips exactly the sick
      chip OPEN — the mesh shrinks N→N−1, the sick device's bucket
      share redistributes to the survivors, and its dispatch counter
      must FREEZE at the trip snapshot (the zero-dispatch-while-OPEN
      proof, asserted from the per-device snapshots in the transition
      log);
    - **recovered**: a canary probe readmits the chip, the mesh
      regrows to N/N, throughput is re-measured.

    On this 1-physical-core host the N virtual devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) make the
    run FUNCTIONAL, not parallel: the headline is the retention ratio
    degraded/healthy (acceptance floor 0.75×(N−1)/N), which on virtual
    devices isolates the mesh-shrink overhead (shard relayout, the
    non-pow2 survivor bucket) rather than real chip capacity. Every
    phase's results are asserted identical to the native oracle.
    """
    import jax

    from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
    from stellar_core_tpu.ops.verifier import ShardedBatchVerifier
    from stellar_core_tpu.util.chaos import ChaosEngine, FaultSpec
    from stellar_core_tpu.util import chaos as chaos_hooks

    host0 = _host_state()
    watch = _HostLoadWatch()
    _enable_compile_cache()
    ndev = len(jax.devices())
    if ndev < 2:
        raise RuntimeError(
            "mesh degradation needs >= 2 devices (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    sick = (ndev - 1) if sick is None else int(sick)
    if batch is None:
        # divisible by both the full mesh and the survivors so neither
        # phase pays a pathological padding blowup (224 on 8 devices:
        # 32 rows/shard healthy, 32 rows/shard degraded)
        batch = 4 * ndev * max(1, ndev - 1)
    pubs, sigs, msgs, lib = _make_batch(batch)
    offsets = np.zeros(batch + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    want = lib.batch_verify(pubs, sigs, b"".join(msgs), offsets)
    assert want.all()
    items = [(bytes(pubs[i]), bytes(sigs[i]), msgs[i])
             for i in range(batch)]

    verifier = ShardedBatchVerifier(device_min_batch=1)
    threshold = 2
    sup = BackendSupervisor(verifier, clock=None,
                            failure_threshold=threshold,
                            probe_base_ms=50.0, probe_max_ms=200.0,
                            canary_batch=32, jitter_seed=seed,
                            chaos_label="mesh-degrade")
    survivors = tuple(i for i in range(ndev) if i != sick)

    def flush() -> None:
        got = sup.verify_tuples(items)
        assert list(got) == [bool(w) for w in want]

    def timed_phase(name: str) -> dict:
        t0 = time.perf_counter()
        for _ in range(flushes):
            flush()
        dt = time.perf_counter() - t0
        tps = batch * flushes / dt
        print("mesh-degrade %-9s %6.1f verifies/s (%d devices active)"
              % (name, tps, len(verifier.active_indices())),
              file=sys.stderr, flush=True)
        return {"tps": round(tps, 1), "flushes": flushes,
                "batch": batch, "wall_s": round(dt, 2),
                "active_devices": len(verifier.active_indices())}

    try:
        # warm every compiled program the phases will ride: the full
        # mesh, the survivor mesh (shrink target) and the pinned
        # single-device canary program — compiles must not contaminate
        # a timed phase
        flush()
        verifier.set_active_devices(survivors)
        verifier.verify_tuples(items)
        verifier.set_active_devices(range(ndev))
        verifier.verify_tuples_async_on(sick, items[:32])()

        healthy = timed_phase("healthy")

        # outage: a device-matched io_error window trips exactly the
        # sick chip (transient class, `threshold` consecutive hits)
        eng = ChaosEngine(seed, [FaultSpec(
            "ops.backend.dispatch.device", "io_error", start=0,
            count=threshold, match={"device": sick})])
        chaos_hooks.install(eng)
        try:
            while sup.status()["devices"][sick]["state"] != "OPEN":
                flush()
        finally:
            chaos_hooks.uninstall()
        st = sup.status()
        assert verifier.active_indices() == survivors
        trip_snap = next(t["device_dispatches"]
                         for t in reversed(st["transitions"])
                         if t["device"] == sick and t["to"] == "OPEN")

        degraded = timed_phase("degraded")

        st = sup.status()
        sick_dispatches_after = st["devices"][sick]["dispatches"]
        quiet = sick_dispatches_after == trip_snap
        aggregate_stayed_closed = st["state"] == "CLOSED"

        # recovery: the canary probe readmits the chip (the io_error
        # window is exhausted), the mesh regrows to N/N
        probe_ok = sup.probe_now(device=sick)
        regrown = verifier.active_indices() == tuple(range(ndev)) \
            and sup.status()["devices"][sick]["state"] == "CLOSED"
        recovered = timed_phase("recovered")

        final = sup.status()
    finally:
        sup.shutdown()

    retention = degraded["tps"] / healthy["tps"]
    floor = 0.75 * (ndev - 1) / ndev
    verdict = {
        "degraded_ok": retention >= floor,
        "retention_floor": round(floor, 4),
        "quiet_while_open": bool(quiet),
        "aggregate_stayed_closed": bool(aggregate_stayed_closed),
        "probe_recovered": bool(probe_ok and regrown),
    }
    verdict["ok"] = all(verdict[k] for k in (
        "degraded_ok", "quiet_while_open", "aggregate_stayed_closed",
        "probe_recovered"))
    return _with_host_state({
        "metric": "mesh_degrade_retention",
        "value": round(retention, 3),
        "unit": "ratio",
        # vs the ideal linear (N-1)/N capacity line: 1.0 = perfect
        # graceful degradation (>1 on virtual devices, where fewer
        # shards mean less relayout work for the one physical core)
        "vs_baseline": round(retention / ((ndev - 1) / ndev), 3),
        "phases": {"healthy": healthy, "degraded": degraded,
                   "recovered": recovered},
        "mesh": {"devices": ndev, "sick_device": sick,
                 "survivors": list(survivors),
                 "injected": dict(eng.injected)},
        "per_device": [
            {k: d[k] for k in ("device", "state", "dispatches",
                               "skips", "consecutive_failures")}
            for d in final["devices"]],
        "quiet_proof": {
            "trip_snapshot": trip_snap,
            "dispatches_after_degraded_phase": sick_dispatches_after,
            "zero_dispatch_while_open": bool(quiet)},
        "transitions": final["transitions"],
        "verdict": verdict,
    }, host0, watch)


def bench_chaos(seed: int = 6, target: int = 12) -> dict:
    """Chaos-convergence scenario (ISSUE 2 tentpole): the canonical
    seeded multinode fault schedule — peer drop, reorder, corruption,
    crash-at-phase-boundary, device-outage window (circuit breaker
    trips, degrades to native, probes, re-closes — ISSUE 5), archive
    fetch failure — run against a fault-free baseline and a repro leg,
    plus a single-node device-outage leg measuring time-to-trip,
    degraded-mode tps and time-to-recovery. value = 1.0 iff liveness+
    safety+reproducibility+breaker+outage-leg all held; the artifact
    carries faults injected per class and recovery data."""
    import shutil
    import tempfile

    from stellar_core_tpu.simulation.chaos import (run_device_outage,
                                                   run_scenario)

    host0 = _host_state()
    watch = _HostLoadWatch()
    root = tempfile.mkdtemp(prefix="bench-chaos-")
    t0 = time.perf_counter()
    try:
        res = run_scenario(seed=seed, target=target,
                           archive_dir=os.path.join(root, "archive"))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    try:
        outage = run_device_outage(seed=seed + 3)
    except Exception as e:                       # noqa: BLE001
        outage = {"ok": False, "error": repr(e)}
    converged = bool(res["liveness_ok"] and res["safety_ok"] and
                     res["repro_ok"] and res.get("archive_ok", True) and
                     res.get("breaker_ok", True) and
                     res.get("clusterstatus_ok", True) and
                     outage.get("ok", False))
    return _with_host_state({
        "metric": "chaos_convergence",
        "value": 1.0 if converged else 0.0,
        "unit": "pass",
        "vs_baseline": 1.0 if converged else 0.0,
        "wall_seconds": round(time.perf_counter() - t0, 1),
        "device_outage": outage,
        **res,
    }, host0, watch)


def bench_replay(seed: int = 7, target: int = 8) -> dict:
    """Whole-node deterministic record/replay (ISSUE 18 tentpole):
    record the seeded 4-node chaos scenario with every node's inputs
    captured (wire frames verbatim, crank/timer phase sequence,
    injections, scripted chaos ordinals), then replay each honest
    survivor TWICE from its log alone and verify (a) header chains and
    controller decision logs byte-identical to the live run, (b) zero
    flight-recorder trace diff between the two replays, (c) the killed
    node's torn log replays to the same crash point, (d) a single
    flipped recorded-frame byte is caught as a first-divergence
    finding with its evidence chain. value = replayed ledgers/sec;
    vs_baseline = replay speed over the live run's ledgers/sec."""
    import copy

    from stellar_core_tpu.replay import log as rlog
    from stellar_core_tpu.replay.replayer import (first_divergence,
                                                  replay_log)
    from stellar_core_tpu.replay.scenario import run_recorded_scenario

    host0 = _host_state()
    watch = _HostLoadWatch()
    t0 = time.perf_counter()
    res = run_recorded_scenario(seed=seed, target=target, trace=True)
    live_wall = time.perf_counter() - t0
    survivors = [h for h in res.logs if h not in res.crashed]

    chains_ok = decisions_ok = ends_ok = traces_ok = True
    ledgers_replayed = 0
    frames_fed = 0
    nodes = {}
    t1 = time.perf_counter()
    for hx in survivors:
        r1 = replay_log(res.logs[hx], trace=True)
        r2 = replay_log(res.logs[hx], trace=True)
        chain_ok = (r1.header_chain == res.chains[hx]
                    and r2.header_chain == res.chains[hx])
        dec_ok = (r1.decisions == res.decisions[hx]
                  and r2.decisions == res.decisions[hx])
        diff = first_divergence(r1.trace, r2.trace)
        chains_ok &= chain_ok
        decisions_ok &= dec_ok
        ends_ok &= bool(r1.end_matches and r2.end_matches)
        traces_ok &= diff is None
        ledgers_replayed += 2 * max(0, r1.lcl_seq - 1)
        frames_fed += r1.frames_fed + r2.frames_fed
        nodes[hx[:8]] = {
            "lcl": r1.lcl_seq, "chain_ok": chain_ok,
            "decisions_ok": dec_ok, "end_ok": bool(r1.end_matches),
            "trace_events": len(r1.trace),
            "trace_diff": None if diff is None else diff["index"],
            "frames": r1.frames_fed,
            "chaos_replayed": r1.chaos_replayed,
        }
    replay_wall = time.perf_counter() - t1

    # the killed node: no END marker, replays up to the recorded
    # stream's end and dies at the same chaos point
    crash_hex = res.crashed[0]
    rc = replay_log(res.logs[crash_hex], trace=False)
    crash_ok = (rc.crashed
                and rc.crash_point == "ledger.close.crash.applyTx")

    # divergence injection: flip one byte inside a recorded frame's
    # envelope signature (the hmac tail is verdict-carried, not read)
    hx = survivors[0]
    clean = replay_log(res.logs[hx], trace=True)
    mut_log = copy.deepcopy(res.logs[hx])
    big = [r for r in mut_log.records
           if r.rtype == rlog.RT_FRAME and len(r.data) > 200]
    victim = big[len(big) // 2]
    raw = bytearray(victim.data)
    raw[-40] ^= 0x01
    victim.data = bytes(raw)
    mutated = replay_log(mut_log, trace=True)
    div = first_divergence(clean.trace, mutated.trace)
    divergence = {"caught": div is not None}
    if div is not None:
        divergence.update({
            "index": div["index"],
            "chain_len": len(div["chain"]),
            "event_a": list(div["a"]) if div["a"] else None,
            "event_b": list(div["b"]) if div["b"] else None,
        })

    verdicts = {
        "chains_match_live": chains_ok,
        "decisions_match_live": decisions_ok,
        "end_markers_match": ends_ok,
        "replays_zero_trace_diff": traces_ok,
        "crash_replayed": crash_ok,
        "divergence_caught": divergence["caught"],
    }
    ok = all(verdicts.values())
    live_lps = (target - 1) / max(live_wall, 1e-9)
    replay_lps = ledgers_replayed / max(replay_wall, 1e-9)
    return _with_host_state({
        "metric": "replay_ledgers_per_sec",
        "value": round(replay_lps, 2),
        "unit": "ledgers/sec",
        "vs_baseline": round(replay_lps / max(live_lps, 1e-9), 2),
        "ok": ok,
        "verdicts": verdicts,
        "nodes": len(res.node_ids),
        "replay": {
            "seed": seed,
            "target": target,
            "survivors": len(survivors),
            "live_wall_s": round(live_wall, 3),
            "replay_wall_s": round(replay_wall, 3),
            "live_ledgers_per_sec": round(live_lps, 2),
            "ledgers_replayed": ledgers_replayed,
            "frames_fed": frames_fed,
            "log_records": {h[:8]: len(l.records)
                            for h, l in res.logs.items()},
            "crashed_node": crash_hex[:8],
            "crash_replay_lcl": rc.lcl_seq,
            "per_node": nodes,
        },
        "divergence": divergence,
    }, host0, watch)


def _newest_artifact_value(prefix: str):
    """Headline value of the newest committed artifact of a family
    (None when absent/failed) — the in-process reference number the
    CLUSTER artifact reports its isolation delta against."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    best, best_round = None, -1
    for f in glob.glob(os.path.join(here, "%s_r*.json" % prefix)):
        m = re.search(r"_r(\d+)\.json$", f)
        if not m or int(m.group(1)) <= best_round:
            continue
        # the NEWEST round decides, even when it recorded a failure or
        # an unreadable file — falling back to an older round's number
        # would compute the isolation delta against a stale baseline
        # with no indication
        best_round = int(m.group(1))
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            best = None
            continue
        v = doc.get("value")
        best = v if isinstance(v, (int, float)) \
            and not isinstance(v, bool) else None
    return best


def bench_tps_cluster(n_orgs: int = 3, validators_per_org: int = 3,
                      trace: bool = False) -> dict:
    """Process-per-node cluster scenario (ROADMAP item 4 / ISSUE 9):
    a ≥9-node tiered quorum of REAL `python -m stellar_core_tpu run`
    subprocesses over real localhost TCP — no shared GIL, no shared
    verify cache — driven entirely through the admin HTTP API
    (simulation/cluster.py). Records wall-clock-faithful pay TPS, the
    flood duplicate ratio over real sockets, per-node close/e2e
    quantiles, the chaos verdicts (seeded bad-sig flood over the
    `chaos` route + a real kill -9 churn with catchup over the wire),
    and the in-process vs multi-process throughput delta against the
    newest TPSM artifact — measured, not guessed."""
    import shutil
    import tempfile

    from stellar_core_tpu.simulation.cluster import run_cluster_scenario

    host0 = _host_state()
    watch = _HostLoadWatch()
    root = tempfile.mkdtemp(prefix="bench-cluster-")
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        res = run_cluster_scenario(
            root, n_orgs=n_orgs, validators_per_org=validators_per_org,
            # production-shaped load for the wire-path verdict
            # (ISSUE 12): 3×1000 txs across 300 accounts. The old
            # 3×300 was sized for the pre-pull-mode harness (82.5 tps,
            # CLUSTER_r09); at that volume the flood duplicate_ratio
            # measures SCP push-gossip redundancy, not the tx wire
            # path the counter exists to judge
            load_accounts=300, load_rounds=3, txs_per_round=1000,
            trace=trace,
            trace_path=os.path.join(here, "trace_cluster.json")
            if trace else None)
    except BaseException:
        # harness errors embed node-log paths under `root` — keep the
        # tree so a failed CLUSTER run is diagnosable
        print(f"cluster scenario failed; node logs kept under {root}",
              file=sys.stderr, flush=True)
        raise
    shutil.rmtree(root, ignore_errors=True)
    in_proc = _newest_artifact_value("TPSM")
    in_proc_tcp = _newest_artifact_value("TPSMT")
    tps = res["tps"]
    return _with_host_state({
        "metric": "loadgen_pay_tps_cluster",
        "value": tps,
        "unit": "txs/sec",
        "vs_baseline": round(tps / 200.0, 3),
        # the delta ROADMAP item 4 demanded be measured, not guessed:
        # this harness's number is the denominator-free ground truth
        # (real processes, real wire); the in-process sims distort via
        # one GIL + a shared verify cache
        "in_process_tps": in_proc,
        "in_process_tcp_tps": in_proc_tcp,
        "isolation_delta_vs_tpsm": round(tps / in_proc, 3)
        if in_proc else None,
        "isolation_delta_vs_tpsmt": round(tps / in_proc_tcp, 3)
        if in_proc_tcp else None,
        **{k: res[k] for k in (
            "nodes", "topology", "applied", "load_wall_s",
            "boot_wall_s", "tps", "flood", "verdicts",
            "clusterstatus_ok", "safety_ok", "liveness_ok",
            "graceful_shutdown_ok", "chaos", "churn",
            "slots_externalized", "wall_seconds", "ok",
            # per-node adaptive-controller snapshots — r11 artifact
            # schema requires them; the harness collected them all
            # along but this key filter silently dropped the section
            "controller",
            # merged cluster-wide series summary + SLO verdicts,
            # scraped per node over the `timeseries`/`slo` routes
            "timeseries", "slo") if k in res},
    }, host0, watch)


def bench_byzantine(seed: int = 7) -> dict:
    """Adversarial-convergence artifact (ISSUE 7): the 9-node tiered
    smoke with one equivocator + one bad-sig flooder against a clean
    leg of the same topology (measured slots-to-externalize and
    verify-service throughput under the flood), plus a tiered churn
    leg — kill a validator mid-close, restart it from persisted state,
    measure catchup-under-chaos recovery. value = 1.0 iff honest
    agreement, flooder dropped, and churn recovery all held."""
    from stellar_core_tpu.simulation.byzantine import run_byzantine_bench

    host0 = _host_state()
    watch = _HostLoadWatch()
    t0 = time.perf_counter()
    res = run_byzantine_bench(seed=seed)
    res["wall_seconds"] = round(time.perf_counter() - t0, 1)
    return _with_host_state(res, host0, watch)


def bench_surge(base_txs: int = 120, surge_txs: int = 1200,
                base_ledgers: int = 4, surge_ledgers: int = 8,
                chunk: int = 30, close_slo_ms: float = 800.0,
                apply_ms_per_tx: float = 2.0) -> dict:
    """Surge-control A/B (ISSUE 11 / ROADMAP item 6): a step-change in
    offered load against a static config vs the adaptive controller.

    One MANUAL_CLOSE standalone node per leg on the VirtualClock, with
    a SYNTHETIC per-tx apply cost (OP_APPLY_SLEEP — the knob the
    reference uses to model slow apply) so close latency is an honest
    linear function of admitted load on any host: ``close_ms ≈
    apply_ms_per_tx × txs + overhead``. The offered schedule is
    identical in both legs — ``base_ledgers`` ledgers at ``base_txs``
    payments, then a step to ``surge_txs`` (the million-users burst) —
    submitted in chunks with a telemetry sample between chunks, which
    is exactly how load accumulates against a 1 Hz sampler on a real
    node during a 5 s ledger interval.

    The static leg admits everything and blows through the close-p99
    SLO; the adaptive leg's controller (ticked once per sample, the
    manual-tick discipline) learns the per-tx close cost during the
    base phase and slams the tx-submit shed gate shut when the pending
    queue exceeds what can close inside the SLO budget — Tail at
    Scale's good-enough-answer-now. Verdict: the adaptive leg records
    ZERO close-p99 breaches and its worst close stays under
    ``close_slo_ms`` while the static leg breaches. Both legs attach
    their PR 10 time-series + SLO sections and the adaptive leg its
    shed/tune decision counts (scripts/check_artifacts.py SURGE
    schema)."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    n_accounts = surge_txs  # one payment per source account per ledger

    def run_leg(adaptive: bool) -> dict:
        cfg = get_test_config()
        cfg.MAX_TX_SET_SIZE = max(2 * surge_txs, 1000)
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
        cfg.SLO_CLOSE_P99_MS = close_slo_ms
        # synthetic apply cost: every tx sleeps apply_ms_per_tx in
        # _apply_transactions — close latency becomes a controlled
        # linear function of admitted load
        cfg.OP_APPLY_SLEEP_TIME_WEIGHT_FOR_TESTING = [1]
        cfg.OP_APPLY_SLEEP_TIME_DURATION_FOR_TESTING = [apply_ms_per_tx]
        app = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.start()
        # account fan-out rides BEFORE the synthetic cost matters
        # (creates batch 100 ops per tx, so setup stays cheap)
        app.manual_close()
        lg = LoadGenerator(app)
        created = 0
        while created < n_accounts:
            created += lg.generate_accounts(
                min(200, n_accounts - created))
            app.manual_close()
            lg.sync_account_seqs()
        app.clock.crank_for(1.0)
        # clean slate for the measured window (the per-leg bench
        # discipline): the fan-out closes must not dilute the close
        # timer the controller learns its per-tx cost from
        app.command_handler.handle("clearmetrics")
        closes_ms = []
        applied_per_ledger = []
        offered_total = submitted_total = 0

        def drive_ledger(offered: int) -> None:
            nonlocal offered_total, submitted_total
            offered_total += offered
            submitted = 0
            sent = 0
            while sent < offered:
                n = min(chunk, offered - sent)
                submitted += lg.generate_payments(n)
                sent += n
                # the 1 Hz cadence: virtual time advances between
                # chunks, a sample lands, and (adaptive leg) the
                # controller ticks against it
                app.clock.crank_for(0.5)
                app.telemetry.sample_now()
                if adaptive:
                    app.controller.tick()
            t0 = time.perf_counter()
            app.manual_close()
            closes_ms.append(
                round((time.perf_counter() - t0) * 1000, 1))
            applied_per_ledger.append(submitted)
            submitted_total += submitted
            lg.sync_account_seqs()
            app.clock.crank_for(1.0)
            app.telemetry.sample_now()
            if adaptive:
                app.controller.tick()

        for _ in range(base_ledgers):
            drive_ledger(base_txs)
        surge_closes_from = len(closes_ms)
        for _ in range(surge_ledgers):
            drive_ledger(surge_txs)
        timeseries, slo = _scenario_reports([app])
        ctl = app.controller.status()
        slo_rules = app.slo.status()["rules"]
        leg = {
            "adaptive": adaptive,
            "offered": offered_total,
            "applied": submitted_total,
            "applied_per_ledger": applied_per_ledger,
            "closes_ms": closes_ms,
            "close_ms_max_surge": max(closes_ms[surge_closes_from:]),
            "close_p99_breaches":
                slo_rules["close_p99"]["breaches"],
            "slo": slo,
            "timeseries": timeseries,
            "shed": ctl["shed"],
            "decisions": {k: v for k, v in ctl["decisions"].items()
                          if k != "tail"},
            "decision_tail": ctl["decisions"]["tail"][-8:],
            "knobs_final": ctl["knobs"],
        }
        app.shutdown()
        return leg

    host0 = _host_state()
    watch = _HostLoadWatch()
    static = run_leg(adaptive=False)
    adaptive = run_leg(adaptive=True)
    static_max = static["close_ms_max_surge"]
    adaptive_max = adaptive["close_ms_max_surge"]
    static_breaches = static["close_p99_breaches"] > 0 \
        or static_max >= close_slo_ms
    adaptive_holds = adaptive["close_p99_breaches"] == 0 \
        and adaptive_max < close_slo_ms
    print("surge A/B: static worst close %.0fms (%d breaches), "
          "adaptive worst close %.0fms (%d breaches), "
          "adaptive shed %d of %d offered" %
          (static_max, static["close_p99_breaches"],
           adaptive_max, adaptive["close_p99_breaches"],
           adaptive["offered"] - adaptive["applied"],
           adaptive["offered"]), file=sys.stderr, flush=True)
    return _with_host_state({
        "metric": "surge_close_p99_control",
        # headline: how many times tighter the adaptive leg held the
        # surge-phase worst close vs static (higher = better)
        "value": round(static_max / max(1.0, adaptive_max), 3),
        "unit": "x",
        "vs_baseline": round(static_max / max(1.0, adaptive_max), 3),
        "slo_close_p99_ms": close_slo_ms,
        "offered_schedule": {
            "base_ledgers": base_ledgers, "base_txs": base_txs,
            "surge_ledgers": surge_ledgers, "surge_txs": surge_txs,
            "apply_ms_per_tx": apply_ms_per_tx},
        "static": static,
        "adaptive": adaptive,
        "verdict": {"static_breaches": bool(static_breaches),
                    "adaptive_holds": bool(adaptive_holds),
                    "ok": bool(static_breaches and adaptive_holds)},
    }, host0, watch)


def bench_trend() -> dict:
    """Perf-trajectory artifact (ISSUE 10): every committed
    ``*_rNN.json`` family folded into a round-by-round headline
    trajectory with host-load annotations and tolerance-gated
    regression flags (scripts/bench_trend.py — also runnable
    standalone, and linted tier-1 so the trajectory can never
    silently go dark again)."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "scripts"))
    try:
        import bench_trend as bt
    finally:
        sys.path.pop(0)
    trend = bt.build_trend(here)
    print(bt.render_table(trend), file=sys.stderr, flush=True)
    return bt.trend_artifact(trend)


def bench_matrix(scale: str = "default") -> dict:
    """Wide-area survival scenario matrix (ISSUE 20,
    scripts/bench_matrix.py): cells over {topology tier, load shape,
    surge, partition window, flap window, slow-link shape, sick-device
    window}, each a real process-per-node cluster with typed
    survival/rejoin/safety/SLO verdicts. Headline value = fraction of
    cells passing, which rides the bench_trend regression gate — a
    change that makes a previously surviving cell fail trips the
    trend, not just this run."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "scripts"))
    try:
        import bench_matrix as bm
    finally:
        sys.path.pop(0)
    import shutil
    import tempfile

    host0 = _host_state()
    watch = _HostLoadWatch()
    root = tempfile.mkdtemp(prefix="bench-matrix-")
    results = bm.run_matrix(root, bm.default_cells(scale))
    art = bm.matrix_artifact(results)
    if art["cells_failed"] == 0:
        shutil.rmtree(root, ignore_errors=True)
    else:
        # failed cells keep node state + per-node input.rec replay
        # logs (the ISSUE 18 flight recorder) for offline diagnosis
        print(f"matrix: {art['cells_failed']} cell(s) failed; node "
              f"state + replay logs kept under {root}",
              file=sys.stderr, flush=True)
    return _with_host_state(art, host0, watch)


def bench_tps(n_accounts: int = 1000, txs_per_ledger: int = 1000,
              n_ledgers: int = 6, n_windows: int = 3,
              trace: bool = False) -> dict:
    """Third BASELINE.md scenario: standalone loadgen PAY TPS.

    Mirrors the reference procedure (`run` on the standalone config +
    HTTP `generateload?mode=pay`, completion-tracked via ledger closes —
    src/main/CommandHandler.cpp:121, src/simulation/LoadGenerator.h:28-35):
    a MANUAL_CLOSE standalone node, accounts fanned out of the root, then
    rate-free max-throughput payment ledgers.  Reported value = applied
    payment txs / wall time covering submission + consensus-free close +
    apply + bucket/DB commit.  vs_baseline = value / 200: the reference
    network's design envelope from BASELINE.md (1000-tx ledgers at the
    ~5 s close cadence, docs/software/performance.md:32).
    """
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    cfg = get_test_config()
    # the reference TPS scenario drives 1000-op ledgers
    # (performance-eval.md:71-79); the genesis header's maxTxSetSize of
    # 100 must be upgraded away or the queue limiter throttles the load
    cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()   # applies the pending testing upgrade
    gen = LoadGenerator(app)
    # the queue caps chained pending txs per source account, so fan the
    # CREATE batches out over several ledgers (reference loadgen spreads
    # them across closes the same way)
    created = 0
    while created < n_accounts:
        created += gen.generate_accounts(min(200, n_accounts - created))
        app.manual_close()
        gen.sync_account_seqs()
    assert created == n_accounts, (created, n_accounts)

    if trace:
        _start_tracing([app])
    host0 = _host_state()
    watch = _HostLoadWatch()
    samples = []
    applied_total = 0
    dt_total = 0.0
    for _ in range(n_windows):
        applied = 0
        t0 = time.perf_counter()
        for _ in range(n_ledgers):
            before = app.ledger_manager.get_last_closed_ledger_num()
            ok = gen.generate_payments(txs_per_ledger)
            app.manual_close()
            assert app.ledger_manager.get_last_closed_ledger_num() == \
                before + 1
            applied += ok
            # manual-close + virtual clock: the recurring sampler
            # never fires, so the bench drives one deterministic
            # sample per measured ledger (ISSUE 10)
            app.telemetry.sample_now()
        dt = time.perf_counter() - t0
        samples.append(round(applied / dt, 1))
        applied_total += applied
        dt_total += dt
    if trace:
        _dump_trace([app], "trace_tps.json")
    # completion check: every submitted payment externalized (queue drained)
    assert gen.failed == 0, gen.failed
    assert not app.herder.tx_queue.get_transactions(), \
        "loadgen payments left in the queue"
    timeseries, slo = _scenario_reports([app])
    app.shutdown()
    # best-of-N windows: the least load-contaminated sample is the
    # recorded headline (VERDICT r04 next-step #2)
    rate = max(samples)
    print("loadgen: %d payments in %.1fs, windows %s" % (
        applied_total, dt_total, samples), file=sys.stderr, flush=True)
    return _with_host_state({
        "metric": "loadgen_pay_tps",
        "value": rate,
        "unit": "txs/sec",
        "vs_baseline": round(rate / 200.0, 3),
        "samples": samples,
        "sustained": round(applied_total / dt_total, 1),
        "timeseries": timeseries,
        "slo": slo,
    }, host0, watch)


def bench_read(n_accounts: int = 1_000_000, write_accounts: int = 200,
               txs_per_ledger: int = 100, n_ledgers: int = 12,
               reader_threads: int = 4, batch: int = 32,
               pin_last: int = 8) -> dict:
    """Snapshot-consistent read serving under write load (ISSUE 17): a
    standalone node seeded with a million-account bucket list serves
    concurrent account reads through the QueryService worker pool while
    the main thread keeps closing payment ledgers.

    Consistency is checked two ways, both of which must come back
    clean for the artifact to claim zero violations:

    - every response's ledger_seq must name a ledger this bench saw
      close (recorded by a closed_hook that runs BEFORE the snapshot
      capture hook, so the set can never lag the snapshots);
    - a sample of responses is re-read against the PINNED snapshot of
      the same seq after the write load finishes — the entry bytes
      must be identical even though later ledgers rewrote the hot
      write-load accounts that are salted into every batch.

    Headline value = successful account reads / wall second over the
    write window; vs_baseline = value / 10_000 (the ISSUE floor)."""
    import random
    import threading

    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import (
        LoadGenerator, bulk_account_id, seed_accounts_bulk)
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.util.timeseries import timer_quantiles

    cfg = get_test_config()
    cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
    cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
    cfg.EXPERIMENTAL_BUCKETLIST_DB = True
    # seeded buckets are ~23MB each: keep them UNDER the index cutoff
    # so lookups stay on the INDIVIDUAL (key->offset) index — measured
    # 13.8us/hit vs 9.5ms for a RANGE page scan, which decodes ~160
    # XDR entries per probe in Python and cannot reach 10k qps
    cfg.EXPERIMENTAL_BUCKETLIST_DB_INDEX_CUTOFF = 64
    cfg.TELEMETRY_SAMPLE_PERIOD = 1.0
    # on this 1-core host a ledger close stalls EVERY in-flight batch
    # past the learned p95 at once (GIL, not a slow lookup) — keep the
    # hedge floor above that microburst so hedges chase real
    # stragglers instead of doubling the load mid-close
    cfg.QUERY_HEDGE_MIN_MS = 25.0
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()   # applies the pending testing upgrade

    # ---- consistency bookkeeping hooks (installed before any load) --
    book_lock = threading.Lock()
    closed_seqs = {app.ledger_manager.get_last_closed_ledger_num()}
    snap_by_seq: dict = {}

    def record_close(header, _lcl_hash):
        with book_lock:
            closed_seqs.add(header.ledgerSeq)

    def pin_snapshot(_header, _lcl_hash):
        snap = app.snapshots.acquire()
        with book_lock:
            snap_by_seq[snap.ledger_seq] = snap
            while len(snap_by_seq) > pin_last:
                app.snapshots.release(snap_by_seq.pop(min(snap_by_seq)))

    # recorder runs BEFORE the SnapshotManager capture hook; the pinner
    # runs AFTER it (appended), so acquire() returns the new snapshot
    app.ledger_manager.closed_hooks.insert(0, record_close)
    app.ledger_manager.closed_hooks.append(pin_snapshot)

    t0 = time.perf_counter()
    seed_accounts_bulk(app, n_accounts)
    seed_s = time.perf_counter() - t0

    gen = LoadGenerator(app)
    created = 0
    while created < write_accounts:
        created += gen.generate_accounts(min(200, write_accounts - created))
        app.manual_close()
        gen.sync_account_seqs()
    write_ids = [a.key.public_key().raw for a in gen.accounts]

    # build the four per-bucket INDIVIDUAL indexes outside the measured
    # window (one probe per seeded level; ~4s each for 250k entries)
    app.query_service.query_accounts(
        [bulk_account_id(i) for i in
         (0, n_accounts // 4, n_accounts // 2, (3 * n_accounts) // 4)],
        deadline_ms=600_000)

    stop = threading.Event()
    stats_lock = threading.Lock()
    counts = {"ok_reads": 0, "shed": 0, "timeouts": 0,
              "seq_mismatches": 0, "responses": 0}
    reread_samples = []

    def reader(seed: int) -> None:
        rng = random.Random(seed)
        svc = app.query_service
        while not stop.is_set():
            # mostly seeded hits, ~2% guaranteed misses (bloom
            # exercise), plus two hot write-load accounts whose bytes
            # change every ledger — the teeth of the re-read check
            ids = [bulk_account_id(rng.randrange(n_accounts),
                                   tag=(b"missing" if rng.random() < 0.02
                                        else b"bigstate"))
                   for _ in range(batch - 2)]
            ids.append(write_ids[rng.randrange(len(write_ids))])
            ids.append(write_ids[rng.randrange(len(write_ids))])
            res = svc.query_accounts(ids)
            if res.get("shed"):
                with stats_lock:
                    counts["shed"] += 1
                continue
            if res.get("timeout") or res.get("error") \
                    or res.get("shutdown"):
                with stats_lock:
                    counts["timeouts"] += 1
                continue
            seq = res["ledger_seq"]
            with book_lock:
                known = seq in closed_seqs
            with stats_lock:
                counts["responses"] += 1
                counts["ok_reads"] += len(ids)
                if not known:
                    counts["seq_mismatches"] += 1
                elif len(reread_samples) < 512 and rng.random() < 0.08:
                    reread_samples.append((seq, ids, res["entries_xdr"]))

    readers = [threading.Thread(target=reader, args=(1000 + i,),
                                daemon=True)
               for i in range(reader_threads)]
    host0 = _host_state()
    watch = _HostLoadWatch()
    for t in readers:
        t.start()
    t0 = time.perf_counter()
    applied = 0
    for _ in range(n_ledgers):
        applied += gen.generate_payments(txs_per_ledger)
        app.manual_close()
        gen.sync_account_seqs()
        app.telemetry.sample_now()
    # a short tail past the last close so reads against the final
    # snapshot land in the sample set too
    time.sleep(0.5)
    dt = time.perf_counter() - t0
    stop.set()
    for t in readers:
        t.join(timeout=10.0)

    # ---- pinned re-read: byte-identity against historical snapshots --
    checked = violations = 0
    with book_lock:
        pinned = dict(snap_by_seq)
    for seq, ids, entries in reread_samples:
        snap = pinned.get(seq)
        if snap is None:
            continue   # aged out of the pin window — nothing to re-read
        again = app.query_service.query_accounts(
            ids, deadline_ms=30_000, snapshot=snap)
        checked += 1
        if again.get("ledger_seq") != seq \
                or again.get("entries_xdr") != entries:
            violations += 1
    with book_lock:
        for snap in snap_by_seq.values():
            app.snapshots.release(snap)
        snap_by_seq.clear()

    qps = counts["ok_reads"] / dt
    rq = timer_quantiles(app.metrics, "query.read.latency")
    sstats = app.query_service.stats()
    issued = sstats["hedge"]["issued"]
    timeseries, slo = _scenario_reports([app])
    app.shutdown()
    print("read bench: %.0f reads/s over %.1fs (%d responses, "
          "%d rechecked, %d violations), write %.0f tps" %
          (qps, dt, counts["responses"], checked, violations,
           applied / dt), file=sys.stderr, flush=True)
    return _with_host_state({
        "metric": "query_read_qps",
        "value": round(qps, 1),
        "unit": "reads/sec",
        "vs_baseline": round(qps / 10_000.0, 3),
        "accounts": n_accounts,
        "seed_s": round(seed_s, 1),
        "read_p50_ms": rq.get("median_ms", 0.0),
        "read_p99_ms": rq.get("p99_ms", 0.0),
        "hedge": {"issued": issued, "won": sstats["hedge"]["won"],
                  "wasted": sstats["hedge"]["wasted"],
                  "rate": round(issued / max(1, counts["responses"]), 4)},
        "consistency": {"responses": counts["responses"],
                        "seq_mismatches": counts["seq_mismatches"],
                        "reread_checked": checked,
                        "reread_violations": violations,
                        "ok": counts["seq_mismatches"] == 0
                        and violations == 0},
        "shed": {"batches": counts["shed"], **sstats["shed"]},
        "timeouts": counts["timeouts"],
        "write": {"ledgers": n_ledgers, "applied": applied,
                  "tps": round(applied / dt, 1)},
        "timeseries": timeseries,
        "slo": slo,
    }, host0, watch)


def bench_apply_parallel(n_accounts: int = 64, txs_per_ledger: int = 48,
                         n_ledgers: int = 4, workers: int = 4,
                         sleep_ms: float = 2.0) -> dict:
    """Conflict-staged parallel apply A/B (ISSUE 16): the same seeded
    payment load driven through APPLY_PARALLEL=<workers> and
    APPLY_PARALLEL=0, under the OP_APPLY_SLEEP per-tx latency model
    (the GIL-releasing portion the staging overlaps — the reference's
    win comes from exactly such non-Python apply work: native verify,
    SQL, host functions). Two load distributions:

    - uniform: payments over rotating disjoint account pairs — the
      friendly cell, wide stages;
    - zipf: the Zipfian hot-account loadgen mode — the adversarial
      cell, conflict chains through the hot accounts.

    Headline value = uniform applyTx-phase speedup (sequential ms /
    parallel ms). The artifact additionally pins byte-identity: per
    distribution, both modes must externalize identical ledger hashes
    close by close."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    host0 = _host_state()
    watch = _HostLoadWatch()

    def applytx_ms(app):
        st = app.perf.report().get("ledger.close.applyTx")
        return st["total_ms"] if st else 0.0

    def drive(dist: str, parallel: int) -> dict:
        # pinned instance: loadgen account keys derive from PEER_PORT,
        # so both modes must see identical ports to build identical txs
        cfg = get_test_config(instance=90)
        cfg.APPLY_PARALLEL = parallel
        cfg.APPLY_PARALLEL_MIN_TXS = 2
        cfg.OP_APPLY_SLEEP_TIME_WEIGHT_FOR_TESTING = [1]
        cfg.OP_APPLY_SLEEP_TIME_DURATION_FOR_TESTING = [sleep_ms]
        cfg.MAX_TX_SET_SIZE = max(2 * txs_per_ledger, 1000)
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = cfg.MAX_TX_SET_SIZE
        app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.start()
        app.manual_close()   # applies the pending testing upgrade
        gen = LoadGenerator(app, seed=1600)
        created = 0
        while created < n_accounts:
            created += gen.generate_accounts(
                min(200, n_accounts - created))
            app.manual_close()
            gen.sync_account_seqs()
        lm = app.ledger_manager
        base_ms = applytx_ms(app)
        hashes = []
        widths: list = []
        stages_total = 0
        ratios = []
        pair = 0
        for _ in range(n_ledgers):
            if dist == "uniform":
                for _ in range(txs_per_ledger):
                    s = gen.accounts[(2 * pair) % len(gen.accounts)]
                    d = gen.accounts[(2 * pair + 1) % len(gen.accounts)]
                    pair += 1
                    gen._sign_and_submit(s, [gen._payment_op(d, 10000)])
            else:
                gen.generate_payments_zipf(txs_per_ledger)
            app.manual_close()
            hashes.append(lm.get_last_closed_ledger_hash().hex())
            widths.extend(lm.last_stage_widths)
            stages_total += lm.last_apply_stages
            n = sum(lm.last_stage_widths)
            ratios.append((lm.last_apply_stages - 1) / (n - 1)
                          if n > 1 else 0.0)
        used_ms = applytx_ms(app) - base_ms
        fallbacks = lm.apply_fallbacks
        failed = gen.failed
        app.shutdown()
        assert failed == 0, failed
        return {"hashes": hashes, "applytx_ms": used_ms,
                "widths": widths, "stages": stages_total,
                "conflict_ratio": round(sum(ratios) / len(ratios), 4),
                "fallbacks": fallbacks}

    legs = {}
    identical = True
    for dist in ("uniform", "zipf"):
        seq_run = drive(dist, 0)
        par_run = drive(dist, workers)
        identical = identical and seq_run["hashes"] == par_run["hashes"]
        speedup = (seq_run["applytx_ms"] / par_run["applytx_ms"]
                   if par_run["applytx_ms"] else 0.0)
        legs[dist] = {
            "parallel_applytx_ms": round(par_run["applytx_ms"], 1),
            "sequential_applytx_ms": round(seq_run["applytx_ms"], 1),
            "speedup": round(speedup, 3),
            "stages": par_run["stages"],
            "max_stage_width": max(par_run["widths"] or [1]),
            "conflict_ratio": par_run["conflict_ratio"],
            "stage_widths": par_run["widths"][:256],
            "fallbacks": par_run["fallbacks"],
        }
        print("apply-parallel %s: seq=%.1fms par=%.1fms speedup=%.2fx "
              "max_width=%d conflict=%.3f identical=%s" % (
                  dist, seq_run["applytx_ms"], par_run["applytx_ms"],
                  speedup, max(par_run["widths"] or [1]),
                  par_run["conflict_ratio"],
                  seq_run["hashes"] == par_run["hashes"]),
              file=sys.stderr, flush=True)
    value = legs["uniform"]["speedup"]
    return _with_host_state({
        "metric": "apply_parallel_speedup",
        "value": value,
        # baseline IS the sequential loop, so the headline ratio is
        # already "vs baseline"
        "vs_baseline": value,
        "unit": "x_applytx_phase",
        "identical": identical,
        "apply_workers": workers,
        "txs_per_ledger": txs_per_ledger,
        "sleep_ms": sleep_ms,
        "legs": legs,
    }, host0, watch)


if __name__ == "__main__":
    # --trace: record a flight-recorder trace over the measured window
    # and write trace_<scenario>.json next to this file (summarize /
    # diff runs with scripts/trace_report.py)
    trace = "--trace" in sys.argv
    if "--catchup" in sys.argv:
        args = [a for a in sys.argv[1:]
                if a not in ("--catchup", "--trace")]
        result = bench_catchup(int(args[0]) if args else 128)
        _record_scenario(result, "CATCHUP")
        print(json.dumps(result))
    elif "--catchup-bigstate" in sys.argv:
        result = bench_catchup_bigstate()
        _record_scenario(result, "CATCHUP_BIGSTATE")
        print(json.dumps(result))
    elif "--tps-multi" in sys.argv:
        print(json.dumps(bench_tps_multinode(trace=trace)))
    elif "--tps-tcp" in sys.argv:
        print(json.dumps(bench_tps_multinode_tcp(trace=trace)))
    elif "--tps-cluster" in sys.argv:
        print(json.dumps(bench_tps_cluster(trace=trace)))
    elif "--tps-soroban" in sys.argv:
        print(json.dumps(bench_tps_soroban()))
    elif "--chaos" in sys.argv:
        print(json.dumps(bench_chaos()))
    elif "--byzantine" in sys.argv:
        print(json.dumps(bench_byzantine()))
    elif "--surge" in sys.argv:
        print(json.dumps(bench_surge()))
    elif "--mesh-degrade" in sys.argv:
        # functional 8-virtual-device mesh when no real multi-chip
        # backend is visible (must precede the first jax import)
        _force_virtual_devices()
        print(json.dumps(bench_mesh_degrade()))
    elif "--read" in sys.argv:
        result = bench_read()
        _record_scenario(result, "READ")
        print(json.dumps(result))
    elif "--bigstate" in sys.argv:
        result = bench_tps_bigstate()
        _record_scenario(result, "TPSM_BIGSTATE")
        print(json.dumps(result))
    elif "--apply-parallel" in sys.argv:
        result = bench_apply_parallel()
        _record_scenario(result, "APPLYPAR")
        print(json.dumps(result))
    elif "--replay" in sys.argv:
        result = bench_replay()
        _record_scenario(result, "REPLAY")
        print(json.dumps(result))
    elif "--matrix" in sys.argv:
        result = bench_matrix(
            "smoke" if "--smoke" in sys.argv else "default")
        _record_scenario(result, "MATRIX")
        print(json.dumps(result))
    elif "--min-batch" in sys.argv:
        print(json.dumps(bench_min_batch()))
    elif "--trend" in sys.argv:
        print(json.dumps(bench_trend()))
    elif "--tps" in sys.argv:
        print(json.dumps(bench_tps(trace=trace)))
    else:
        main()
