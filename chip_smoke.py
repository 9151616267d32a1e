#!/usr/bin/env python3
"""The quickest proof that stellar-core-tpu still starts on the chip.

    python chip_smoke.py                 # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4       # only the four-chip mesh check

Drives the main path through `python -m stellar_core_tpu`, at the width
of BASELINE.json config 1 / docs/stellar-core-tpu_standalone.cfg (1,000
PaymentOp transactions per close over 1,000 accounts), with
SIGNATURE_VERIFY_BACKEND = "tpu":

  run           a standalone validator closes one checkpoint (64
                ledgers, three of them full) and publishes it
  catchup       a second node replays it from the archive — the phase
                in which the chip verifies the checkpoint's signatures
  reference     the same replay with the native verifier: same LCL, hash
  differential  the adversarial Ed25519 corpus, chip against the oracle

Standard library only, and NO JAX in this process: a chip belongs to
one process at a time, so every phase is a child that has exited before
the next starts, and the device named in the last line is the one the
child that owned the chip reported. Every phase prints one JSON object;
the first failed check exits non-zero. The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only when every phase passed on a TPU. With --rehearse
(CPU rehearsal at sizes lowered by the options below) every phase runs
on whatever device JAX has and the script still fails at that line.
"""

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKPOINT = 63                      # first checkpoint: ledgers 1..63
PASSPHRASE = "Standalone Network ; February 2017"


class Failed(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ------------------------------------------------------------ children ----

def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    # JAX's own switch: every compile is logged with its shapes and
    # seconds, which is how a cold run and a warm one are told apart
    env["JAX_LOG_COMPILES"] = "1"
    return env


def cache_root() -> str:
    """Where the children keep compiled programs (util/jax_cache.py's
    rule, restated here because this process must not import JAX)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_compile_cache")


def cache_entries() -> int:
    return sum(len(files) for _, _, files in os.walk(cache_root()))


_COMPILING = re.compile(r"Compiling jit\((\w+)\) with global shapes and "
                        r"types \(ShapedArray\((\w+\[[\d,]*\])")
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for 'jit_(\w+)'")
_FINISHED = re.compile(r"Finished XLA compilation of jit\((\w+)\) in "
                       r"([0-9.eE+-]+) sec")


def compiles_in(log_path: str, min_secs: float = 1.0) -> list:
    """[{fn, shape, secs, cache_hit}] for every XLA compilation the
    child logged (JAX_LOG_COMPILES) that took at least `min_secs`: the
    first call of each bucket. The verify kernels take minutes cold and
    seconds from a warm cache; the rest are tiny helpers. A node logs
    each line twice (JAX's handler and its own), hence `seen`."""
    shape, hit, seen, out = {}, set(), set(), []
    try:
        with open(log_path, errors="replace") as f:
            for line in f:
                if "ompil" not in line:
                    continue
                m = _COMPILING.search(line)
                if m:
                    shape[m.group(1)] = m.group(2)
                    hit.discard(m.group(1))
                m = _CACHE_HIT.search(line)
                if m:
                    hit.add(m.group(1))
                m = _FINISHED.search(line)
                if m and float(m.group(2)) >= min_secs \
                        and m.groups() not in seen:
                    seen.add(m.groups())
                    out.append({"fn": m.group(1),
                                "shape": shape.get(m.group(1), ""),
                                "secs": round(float(m.group(2)), 2),
                                "cache_hit": m.group(1) in hit})
    except OSError:
        pass
    return out


def run_child(name: str, argv: list, out_dir: str, timeout: float):
    """Run one child to its end; returns (rc, stdout text, log path)."""
    log_path = os.path.join(out_dir, f"{name}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=child_env(),
                                stdout=subprocess.PIPE, stderr=log,
                                text=True)
        try:
            stdout, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise Failed(f"{name}: no end within {timeout:.0f}s")
    with open(os.path.join(out_dir, f"{name}.out"), "w") as f:
        f.write(stdout)
    return proc.returncode, stdout, log_path


def last_json_line(text: str, key: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if key in doc:
                return doc
    raise Failed(f"child printed no JSON line with {key!r}")


# -------------------------------------------------------------- checks ----

def check_supervisor(phase: str, status: dict) -> None:
    """The supervisor turns any device failure into native answers and
    the process still exits 0; the smoke is what must not be fooled."""
    check(status.get("state") == "CLOSED",
          f"{phase}: supervisor state {status.get('state')!r}, not CLOSED")
    check(status.get("transition_count") == 0,
          f"{phase}: supervisor made {status.get('transition_count')} "
          "transitions")
    check(status.get("skips") == 0,
          f"{phase}: {status.get('skips')} dispatches skipped the device")
    failures = status.get("failures") or {}
    check(not any(failures.values()),
          f"{phase}: device failures {failures}")


def check_device(phase: str, device, want_count: int, rehearse: bool):
    check(isinstance(device, dict) and
          {"platform", "kind", "count"} <= set(device),
          f"{phase}: no device reported ({device!r})")
    if not rehearse:
        check(device["platform"] == "tpu",
              f"{phase}: verifier runs on {device['platform']!r} "
              f"({device['kind']}), not on a TPU")
        check(device["count"] == want_count,
              f"{phase}: {device['count']} devices, wanted {want_count}")
    return device


def write_config(args, path: str, workdir: str, archive: str, backend: str,
                 put: bool) -> None:
    """The documented standalone config (docs/stellar-core-tpu_
    standalone.cfg) with an ephemeral admin port, absolute paths under
    the output directory, the verify backend, and a tx-set limit that
    admits the documented 1,000 operations per close (genesis has 100;
    the upgrade lands with the first close)."""
    os.makedirs(workdir, exist_ok=True)
    max_txs = max(1000, args.txs, args.accounts)
    lines = [
        "RUN_STANDALONE = true",
        "MANUAL_CLOSE = true",
        "HTTP_PORT = 0",
        f'NETWORK_PASSPHRASE = "{PASSPHRASE}"',
        f'DATABASE = "sqlite3://{workdir}/stellar.db"',
        f'BUCKET_DIR_PATH = "{workdir}/buckets"',
        "METADATA_DEBUG_LEDGERS = 512",
        f'SIGNATURE_VERIFY_BACKEND = "{backend}"',
        f"MAX_TX_SET_SIZE = {max_txs}",
        f"TESTING_UPGRADE_MAX_TX_SET_SIZE = {max_txs}",
        "",
        "[HISTORY.local]",
        f'get = "cp {archive}/{{0}} {{1}}"',
    ]
    if put:
        lines.append(f'put = "mkdir -p $(dirname {archive}/{{1}}) && '
                     f'cp {{0}} {archive}/{{1}}"')
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


class Node:
    """A `run` child and its admin port."""

    def __init__(self, conf: str, out_dir: str):
        self.log_path = os.path.join(out_dir, "run.log")
        port_file = os.path.join(out_dir, "run.port")
        if os.path.exists(port_file):
            os.unlink(port_file)
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "stellar_core_tpu", "--conf", conf,
             "run", "--new-db", "--port-file", port_file],
            cwd=ROOT, env=child_env(), stdout=self._log,
            stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 180
        while not os.path.exists(port_file):
            check(self.proc.poll() is None,
                  f"run: the node exited with code {self.proc.returncode} "
                  f"before it served (see {self.log_path}): {self.tail()}")
            check(time.monotonic() < deadline, "run: no admin port in 180s")
            time.sleep(0.1)
        with open(port_file) as f:
            self.port = int(f.read())

    def tail(self, n: int = 600) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def get(self, command: str) -> dict:
        """One admin command. The node answers from its main loop, and
        gives up after 30 s with an `exception` while the command may
        STILL execute (a cold compile inside a close takes minutes):
        callers poll for the effect, they never send a command twice."""
        check(self.proc.poll() is None,
              f"run: the node died (code {self.proc.returncode}): "
              f"{self.tail()}")
        url = f"http://127.0.0.1:{self.port}/{command}"
        with urllib.request.urlopen(url, timeout=120) as resp:
            return json.loads(resp.read().decode())

    def poll(self, command: str, done, what: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            doc = self.get(command)
            if "exception" not in doc and done(doc):
                return doc
            check(time.monotonic() < deadline,
                  f"run: {what} not seen within {timeout:.0f}s "
                  f"(last answer {json.dumps(doc)[:300]})")
            time.sleep(0.2)

    def lcl(self) -> dict:
        return self.poll("info", lambda d: True, "info", 900)["info"]["ledger"]

    def close(self, timeout: float = 900) -> dict:
        """manualclose, then wait until the ledger number moved."""
        before = self.lcl()["num"]
        self.get("manualclose")
        return self.poll("info",
                         lambda d: d["info"]["ledger"]["num"] > before,
                         f"ledger {before + 1}", timeout)["info"]["ledger"]

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


def metric(metrics: dict, name: str, field: str = "count", default=0):
    return (metrics.get(name) or {}).get(field, default)


# -------------------------------------------------------------- phases ----

def phase_run(args, out_dir: str, archive: str) -> dict:
    t0 = time.monotonic()
    entries0 = cache_entries()
    conf = os.path.join(out_dir, "run.cfg")
    write_config(args, conf, os.path.join(out_dir, "run-node"), archive,
                 "tpu", put=True)
    node = Node(conf, out_dir)
    try:
        status = node.poll("backendstatus", lambda d: "backend" in d,
                           "backendstatus", 600)["backend"]
        device = check_device("run", status.get("device"), 1, args.rehearse)

        def tx_count() -> int:
            return metric(node.get("metrics")["metrics"],
                          "ledger.transaction.count")

        node.close()                       # ledger 2: tx-set size upgrade
        made = node.get(f"generateload?mode=create&accounts={args.accounts}")
        check(made.get("submitted") == args.accounts,
              f"run: generateload created {made}")
        node.close()
        applied = []
        for _ in range(args.full_ledgers):
            n0 = tx_count()
            sent = node.poll(
                f"generateload?mode=pay&txs={args.txs}",
                lambda d: True, "generateload", 600)
            check(sent.get("submitted") == args.txs,
                  f"run: generateload submitted {sent}, wanted {args.txs}")
            led = node.close()
            applied.append({"ledger": led["num"],
                            "txs": tx_count() - n0})
            check(applied[-1]["txs"] == args.txs,
                  f"run: ledger {led['num']} applied {applied[-1]['txs']} "
                  f"transactions, wanted {args.txs}")
        led = node.lcl()
        check(led["num"] < CHECKPOINT, f"run: already at ledger {led['num']}")
        while led["num"] < CHECKPOINT:
            led = node.close()
        lcl_hash = led["hash"]
        # ledger 63 closes the checkpoint; its publish is queued by the
        # NEXT close — close on until the archive holds it
        has = os.path.join(archive, ".well-known", "stellar-history.json")
        deadline = time.monotonic() + 300
        while True:
            try:
                with open(has) as f:
                    if json.load(f).get("currentLedger", 0) >= CHECKPOINT:
                        break
            except (OSError, ValueError):
                pass
            check(time.monotonic() < deadline,
                  "run: checkpoint 63 not in the archive within 300s")
            if node.lcl()["num"] < CHECKPOINT + 4:
                node.close()
            else:
                time.sleep(0.2)
        status = node.get("backendstatus")["backend"]
        doc = node.get("metrics")
        metrics, zones = doc["metrics"], doc.get("perf_zones", {})
    finally:
        rc = node.stop()
    check(rc == 0, f"run: the node exited with code {rc} after SIGTERM")
    check_supervisor("run", status)
    on_device = metric(metrics, "crypto.verify.dispatch.batch", "sum")
    signatures = args.full_ledgers * args.txs
    flushes = {k.rsplit(".", 1)[1]: v.get("count", 0)
               for k, v in metrics.items()
               if k.startswith("crypto.verify_service.flush.")}
    return {
        "phase": "run", "ok": True, "device": device,
        "reduced": "depth: one checkpoint (64 ledgers) with "
                   f"{args.full_ledgers} full ledgers in it; width kept: "
                   f"{args.txs} PaymentOp transactions per full close over "
                   f"{args.accounts} accounts",
        "lcl": CHECKPOINT, "lcl_hash": lcl_hash, "full_ledgers": applied,
        "supervisor": {k: status[k] for k in
                       ("state", "transition_count", "skips", "failures",
                        "dispatches")},
        "device_signatures": {
            "dispatches": metric(metrics, "crypto.verify.dispatch.batch"),
            "sum": on_device,
            "of_payment_signatures": signatures,
            "share": on_device / signatures if signatures else None},
        "verify_service": {
            "flushes_by_reason": flushes,
            "fallback": metric(metrics, "crypto.verify_service.fallback"),
            "native_bypass_zone": (zones.get("crypto.batchVerify.native")
                                   or {}).get("count", 0)},
        "ledger_close_mean_ms": {
            k: round(v.get("mean_ms", 0.0), 3) for k, v in sorted(
                zones.items()) if k.startswith("ledger.close")},
        "compiles": compiles_in(node.log_path),
        "cache_entries_added": cache_entries() - entries0,
        "seconds": round(time.monotonic() - t0, 1)}


def phase_catchup(args, out_dir: str, archive: str, backend: str,
                  name: str, want_hash: str) -> dict:
    t0 = time.monotonic()
    entries0 = cache_entries()
    conf = os.path.join(out_dir, f"{name}.cfg")
    write_config(args, conf, os.path.join(out_dir, f"{name}-node"), archive,
                 backend, put=False)
    rc, stdout, log_path = run_child(
        name, [sys.executable, "-m", "stellar_core_tpu", "--conf", conf,
               "catchup", "current", "--new-db"], out_dir, args.timeout)
    check(rc == 0, f"{name}: catchup exited with code {rc}: "
                   f"{stdout[-300:]}")
    doc = last_json_line(stdout, "lcl_hash")
    check(doc["state"] == "WORK_SUCCESS", f"{name}: ended {doc['state']}")
    check(doc["lcl"] == CHECKPOINT,
          f"{name}: LCL {doc['lcl']}, wanted {CHECKPOINT}")
    check(doc["lcl_hash"] == want_hash,
          f"{name}: hash {doc['lcl_hash']} differs from phase run's "
          f"{want_hash}")
    out = {"phase": name, "ok": True, "verify_backend": backend,
           "lcl": doc["lcl"], "lcl_hash": doc["lcl_hash"]}
    if backend == "tpu":
        status = doc["backend"]
        out["device"] = check_device(name, status.get("device"), 1,
                                     args.rehearse)
        check_supervisor(name, status)
        batch = doc["crypto.verify.dispatch.batch"]
        signatures = args.full_ledgers * args.txs
        check(batch["sum"] >= signatures,
              f"{name}: {batch['sum']} signatures reached the device, "
              f"the full ledgers alone carry {signatures}")
        out["supervisor"] = {k: status[k] for k in
                             ("state", "transition_count", "skips",
                              "failures", "dispatches")}
        out["device_signatures"] = {
            "dispatches": batch["count"], "sum": batch["sum"],
            "of_payment_signatures": signatures}
        out["compiles"] = compiles_in(log_path)
        out["cache_entries_added"] = cache_entries() - entries0
    out["seconds"] = round(time.monotonic() - t0, 1)
    return out


def phase_differential(args, out_dir: str) -> dict:
    t0 = time.monotonic()
    entries0 = cache_entries()
    rc, stdout, log_path = run_child(
        "differential",
        [sys.executable, os.path.join(ROOT, "scripts", "tpu_differential.py"),
         "run", "--out", os.path.join(out_dir, "differential.npz"),
         "--n", str(args.diff_n), "--msg32-fill", str(args.txs)],
        out_dir, args.timeout)
    check(rc == 0, f"differential: chip and oracle disagree or the child "
                   f"failed (code {rc}): {stdout[-400:]}")
    doc = last_json_line(stdout, "mismatches_vs_oracle")
    check(doc["mismatches_vs_oracle"] == 0, f"differential: {doc}")
    device = check_device("differential", doc.get("device"), 1,
                          args.rehearse)
    return {"phase": "differential", "ok": True, "device": device,
            "signatures": doc["n"], "signatures_msg32": doc["n_msg32"],
            "mismatches_vs_oracle": 0,
            "verify_secs": {"full": doc["secs"], "msg32": doc["secs_msg32"]},
            "compiles": compiles_in(log_path),
            "cache_entries_added": cache_entries() - entries0,
            "seconds": round(time.monotonic() - t0, 1)}


def phase_mesh(args, out_dir: str) -> dict:
    """--chips 4: ShardedBatchVerifier over the four chips against
    TpuBatchVerifier on one and the oracle, in ONE child that owns all
    four (scripts/mesh_differential.py)."""
    t0 = time.monotonic()
    rc, stdout, log_path = run_child(
        "mesh",
        [sys.executable, os.path.join(ROOT, "scripts", "mesh_differential.py"),
         "--devices", str(args.chips), "--n", str(args.diff_n),
         "--batch", str(args.mesh_batch)], out_dir, args.timeout)
    check(rc == 0, f"mesh: the child failed (code {rc}): {stdout[-400:]}")
    doc = last_json_line(stdout, "shard_devices")
    check(doc.get("ok") is True, f"mesh: {doc}")
    device = check_device("mesh", doc.get("device"), args.chips,
                          args.rehearse)
    doc.update({"phase": "mesh", "device": device,
                "compiles": compiles_in(log_path),
                "seconds": round(time.monotonic() - t0, 1)})
    return doc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="everything is written under this directory")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the four-chip mesh check")
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on whatever device JAX has "
                         "(a CPU rehearsal); never prints ok")
    ap.add_argument("--accounts", type=int, default=1000)
    ap.add_argument("--txs", type=int, default=1000,
                    help="PaymentOp transactions per full ledger")
    ap.add_argument("--full-ledgers", type=int, default=3)
    ap.add_argument("--diff-n", type=int, default=200,
                    help="random tuples ahead of the adversarial corpus "
                         "(tpu_differential's fast tier)")
    ap.add_argument("--mesh-batch", type=int, default=4096,
                    help="--chips 4: size of the valid batch")
    ap.add_argument("--timeout", type=float, default=1100.0,
                    help="seconds one child may take")
    args = ap.parse_args()

    out_dir = os.path.abspath(args.out)
    archive = os.path.join(out_dir, "archive")
    # a fresh run: nodes start from --new-db and the archive is theirs
    subprocess.run(["rm", "-rf", archive] + [
        os.path.join(out_dir, d) for d in
        ("run-node", "catchup-node", "reference-node")], check=True)
    os.makedirs(out_dir, exist_ok=True)

    t0 = time.monotonic()
    device = None
    try:
        if args.chips == 4:
            mesh = phase_mesh(args, out_dir)
            emit(mesh)
            device = mesh["device"]
        else:
            run = phase_run(args, out_dir, archive)
            emit(run)
            catchup = phase_catchup(args, out_dir, archive, "tpu",
                                    "catchup", run["lcl_hash"])
            emit(catchup)
            emit(phase_catchup(args, out_dir, archive, "native",
                               "reference", run["lcl_hash"]))
            diff = phase_differential(args, out_dir)
            emit(diff)
            check(run["device"] == catchup["device"] == diff["device"],
                  "the phases ran on different devices")
            device = catchup["device"]
    except Failed as e:
        emit({"ok": False, "failed": str(e),
              "seconds": round(time.monotonic() - t0, 1)})
        return 1
    if device["platform"] != "tpu" or device["count"] != args.chips:
        emit({"ok": False, "device": device,
              "failed": f"every phase ran, but on {device['platform']!r} x "
                        f"{device['count']}: a chip smoke passes only on "
                        f"{args.chips} TPU chip(s)",
              "seconds": round(time.monotonic() - t0, 1)})
        return 1
    emit({"summary": True, "seconds": round(time.monotonic() - t0, 1),
          "cache_root": cache_root()})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
