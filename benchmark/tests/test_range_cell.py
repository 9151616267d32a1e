"""The cell `multisig-range.range-replay`, rehearsed at tiny size on the
CPU as test_multisig_cell.py rehearses the dense one: the added
configuration, traffic mix and entries lie under `data/added/`,
`rehearse.make_root` copies the files, and this file lays its own
entries over the root that makes. The largest bucket is patched to 16
lanes. A sound run comes out correct, with a candidate from the carried
signer keys in every replay; under the control of range_controls.py
(a resolver that drops the carried keys) not correct."""

import io
import json
import os
import time

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import rehearse as R
from benchmark.tests.range_controls import CONTROLS

CELL = "tiny-range.tiny-range-replay"
REAL = "multisig-range.range-replay"


def make_root(tmp: str) -> str:
    root = R.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(R.ADDED, "BENCHMARK.add.range.json")) as f:
        add = json.load(f)
    doc["configs"] += add["configs"]
    doc["workloads"] += add["workloads"]
    for m in doc["end_to_end"]:
        m_more = add["end_to_end_workloads"].get(m["name"])
        if m_more:
            m["workloads"] = m["workloads"] + m_more
    # the tiny cell reports every per-layer metric the real one does
    for m in doc["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return root


@pytest.fixture(autouse=True)
def tiny_program(monkeypatch):
    """16 lanes a chunk; and the program's own resolver under catchup,
    whatever a control of an earlier test file left there."""
    from stellar_core_tpu.catchup import catchup_work
    from stellar_core_tpu.ops import chunking
    from stellar_core_tpu.tx import signature_checker
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)
    monkeypatch.setattr(catchup_work, "collect_signature_tuples",
                        signature_checker.collect_signature_tuples)


def run(tmp_path, control=None, trace=0):
    from benchmark.harness.main import main
    out = io.StringIO()
    rc = main(["--workload", CELL, "--seed", "4294967335", "--seconds", "2",
               "--trace", str(trace)], t0=time.perf_counter(),
              root=make_root(str(tmp_path)), require_chip=False, out=out,
              driver_hook=CONTROLS[control] if control else None)
    assert rc == 0
    lines = out.getvalue().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_traced(tmp_path):
    doc, lines = run(tmp_path, trace=1)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is True and not failed, failed
    # whole replays of ledgers 2..127
    assert doc["failed"] == 0 and doc["attempted"] % 126 == 0 \
        and doc["attempted"] > 0
    assert doc["device"]["platform"] == "cpu"
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    spec = Spec.load(str(tmp_path))
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if CELL in m.get("workloads", ())}
    # 19 of its own and `jit_trace_lower_s`, which it shares
    assert len(mine) == 20 and "jit_trace_lower_s" in mine
    from_device = {n for n, m in mine.items()
                   if m["source"] == "device_trace"}
    got = doc["metrics"]
    # all but the three that only a device trace gives; and at this
    # size on the CPU apply may be over before a chunk has landed
    assert set(mine) - from_device - {"chunk_adopt_lag_ms.range"} \
        <= set(got) <= set(mine) - from_device
    assert ("chunk_adopt_lag_ms.range" in got) == \
        (got["prevalidated_hit_share.range"]["value"] > 0.0)
    assert got["resolver_miss_share.range"]["value"] == 0.0
    assert got["device_sig_share.range"]["value"] >= 100.0
    assert got["prevalidated_hit_share.range"]["value"] + \
        got["pending_miss_share.range"]["value"] == pytest.approx(100.0)
    for name in ("batch_lead_ms.range", "prefetch_ahead_ms.range",
                 "collect_wait_ms.range", "collect_tuples_ms.range"):
        assert got[name]["value"] >= 0.0
    assert got["prefetch_ahead_ms.range"]["value"] > \
        got["collect_tuples_ms.range"]["value"] / 2
    checks = [ln for ln in lines if ln.startswith("check: ")]
    for what in ("resolver never made", "crypto.collect.carried",
                 "other than 2 batches", "differs from the publisher's",
                 "published (2) off the deployment's 2",
                 "adversarial envelopes (of 10)"):
        assert any(what in ln for ln in checks), what


def test_control_is_not_correct(tmp_path):
    doc, lines = run(tmp_path, "range.resolver_drops_carried")
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is False
    # by both: the table was asked for tuples nobody made, and no
    # candidate came from the carry; the chain is still the publisher's
    assert any("resolver never made" in ln for ln in failed), failed
    assert any("crypto.collect.carried" in ln for ln in failed), failed
    assert not any("differs from the publisher's" in ln for ln in failed)


def test_real_cell_is_declared_with_its_files():
    spec = Spec.load(R.ROOT)
    wl = spec.workload(REAL)
    assert wl["chips"] == 1
    cfg = spec.config(wl["config"])
    dense = spec.config("multisig-dense")
    dep, was = cfg["deployment"], dense["deployment"]
    # multisig-dense's shapes, copied and not cut
    for key in ("accounts", "txs_per_ledger", "classes", "amounts",
                "signatures_per_payment_ledger", "starting_balance",
                "multisig_share_percent"):
        assert dep[key] == was[key], key
    assert cfg["node"] == dense["node"]
    assert cfg["publisher_overrides"] == dense["publisher_overrides"]
    assert set(dense["assumed"]) <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == len(dense["guarantees"]) + 1
    # what differs: the range
    assert (dep["checkpoint"], dep["checkpoints"]) == (127, 2)
    assert dep["payment_ledgers"] == 123 == 127 - 4
    rot = dep["rotation"]
    assert rot["payment_ledgers"] == list(range(10, 121, 10))
    assert rot["payment_ledgers"][:5] == was["rotation"]["payment_ledgers"]
    # the adversarial envelopes take six accounts of every kind
    assert rot["pool"] >= 6 and \
        dep["classes"][rot["class"]]["accounts"] - rot["pool"] >= 6
    # every account of the pool rotates in checkpoint 63 (payment ledgers
    # 1..59) and again in checkpoint 127
    per = rot["accounts_per_ledger"]
    in_first = sum(per for p in rot["payment_ledgers"] if p <= 59)
    assert in_first == rot["pool"] and \
        per * len(rot["payment_ledgers"]) >= 2 * rot["pool"]
    assert cfg["reduced"] == ["ledgers"] and cfg["what_the_cut_hides"]
    traffic = spec.traffic(wl["traffic"])
    assert traffic["generator"] == "range_replay"
    assert traffic["params"]["checkpoints"] == dep["checkpoints"]
    dense_traffic = spec.traffic("dense-replay")["params"]
    assert {k: v for k, v in traffic["params"].items()
            if k != "checkpoints"} == dense_traffic
    mine = [m for m in spec.doc["per_layer"]
            if REAL in m["workloads"] and m["name"].endswith(".range")]
    assert len(mine) == 19 and all(m["workloads"] == [REAL] for m in mine)
    assert all(m["moves"] == "catchup_ledgers_per_s" for m in mine)
    shared = [m["name"] for m in spec.doc["per_layer"]
              if REAL in m["workloads"] and m not in mine]
    assert shared == ["jit_trace_lower_s"]
    for m in spec.doc["end_to_end"]:
        if m["name"] == "catchup_ledgers_per_s":
            assert m["workloads"][-1] == REAL
