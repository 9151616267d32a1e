"""Build nodes in-process the way `cmd_run` / `cmd_catchup` do, and read
what they count.

From the program this module takes the system under test
(`Application`, `Config`, the ledger root) and its counters, zones and
supervisor status; the arithmetic on them is the benchmark's.
"""

import os

from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.main import Application, Config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger_entries import LedgerKey
from stellar_core_tpu.xdr.types import PublicKey


def make_config(node_cfg: dict, workdir: str, archive_root=None,
                put: bool = False, overrides=None) -> Config:
    """`Config.from_dict` of the configuration file's `node` table (the
    keys of docs/stellar-core-tpu_standalone.cfg), with the paths of
    this run: sqlite database and bucket directory on disk under
    `workdir`, and a `cp` history archive when `archive_root` is set."""
    os.makedirs(workdir, exist_ok=True)
    doc = dict(node_cfg)
    doc.update(overrides or {})
    doc["DATABASE"] = f"sqlite3://{workdir}/stellar.db"
    doc["BUCKET_DIR_PATH"] = f"{workdir}/buckets"
    if archive_root is not None:
        cmds = {"get": f"cp {archive_root}/{{0}} {{1}}"}
        if put:
            cmds["put"] = (f"mkdir -p $(dirname {archive_root}/{{1}}) && "
                           f"cp {{0}} {archive_root}/{{1}}")
        doc["HISTORY"] = {"local": cmds}
    return Config.from_dict(doc)


def start_node(cfg: Config):
    """A started node on the real-time clock (what `run` and `catchup`
    build), with a new database."""
    app = Application.create(VirtualClock(ClockMode.REAL_TIME), cfg,
                             new_db=True)
    app.start()
    return app


def account_states(app, raw_keys) -> dict:
    """{raw public key: (balance, sequence number)} read from the
    node's committed ledger state."""
    out = {}
    with LedgerTxn(app.ledger_manager.root) as ltx:
        for raw in raw_keys:
            le = ltx.load_without_record(
                LedgerKey.account(PublicKey.ed25519(raw)))
            if le is not None:
                acc = le.data.value
                out[raw] = (acc.balance, acc.seqNum)
    return out


def account_seq(app, raw: bytes) -> int:
    return account_states(app, [raw]).get(raw, (0, 0))[1]


# ------------------------------------------------- counters and zones ----

def counters(app) -> dict:
    """{name: (count, sum)} of every metric of the node's registry."""
    return {name: (m.get("count", 0), m.get("sum", 0.0))
            for name, m in app.metrics.to_json().items()}


def zones(app) -> dict:
    """{zone: (count, total seconds)} of the node's perf zones."""
    return {name: (z["count"], z["total_ms"] / 1e3)
            for name, z in app.perf.report().items()}


def add_into(total: dict, part: dict, minus: dict = None) -> None:
    """total += part - minus, pairwise over (count, sum) tuples."""
    for name, (c, s) in part.items():
        c0, s0 = (minus or {}).get(name, (0, 0.0))
        tc, ts = total.get(name, (0, 0.0))
        total[name] = (tc + c - c0, ts + s - s0)


def supervisor_faults(status) -> list:
    """What `chip_smoke.py`'s `check_supervisor` refuses, as a list of
    complaints (empty = clean): the supervisor turns any device failure
    into native answers and carries on, so the benchmark must look."""
    if not status:
        return ["no backend supervisor: the node has no device verifier"]
    bad = []
    if status.get("state") != "CLOSED":
        bad.append(f"supervisor state {status.get('state')!r}")
    if status.get("transition_count"):
        bad.append(f"{status['transition_count']} breaker transitions")
    if status.get("skips"):
        bad.append(f"{status['skips']} dispatches skipped the device")
    failures = {k: v for k, v in (status.get("failures") or {}).items()
                if v}
    if failures:
        bad.append(f"device failures {failures}")
    if status.get("quarantined"):
        bad.append(f"quarantined handles {status['quarantined']}")
    return bad
