"""The cell `multisig-dense.dense-replay`, rehearsed at tiny size on the
CPU as the two cells before it are (test_data_driven.py): the added
configuration, traffic mix and entries lie under `data/added/` beside
theirs, `rehearse.make_root` copies the files, and this file lays its
own entries over the root that makes. The largest bucket is patched to
16 lanes, so the tiny archive's tuples run as dozens of chunks of a
shape the CPU suite compiles anyway. Sound runs come out correct, and
under either control of multisig_controls.py not correct."""

import io
import json
import os
import time

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import rehearse as R
from benchmark.tests.multisig_controls import CONTROLS

CELL = "tiny-multisig.tiny-dense-replay"
REAL = "multisig-dense.dense-replay"


def make_root(tmp: str) -> str:
    root = R.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(R.ADDED, "BENCHMARK.add.multisig.json")) as f:
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


@pytest.fixture
def bucket16(monkeypatch):
    from stellar_core_tpu.ops import chunking
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)


def run(tmp_path, control=None, trace=0):
    from benchmark.harness.main import main
    out = io.StringIO()
    rc = main(["--workload", CELL, "--seed", "4294967311", "--seconds", "2",
               "--trace", str(trace)], t0=time.perf_counter(),
              root=make_root(str(tmp_path)), require_chip=False, out=out,
              driver_hook=CONTROLS[control] if control else None)
    assert rc == 0
    lines = out.getvalue().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_traced(tmp_path, bucket16):
    doc, lines = run(tmp_path, trace=1)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is True and not failed, failed
    assert doc["failed"] == 0 and doc["attempted"] >= 62
    assert doc["device"]["platform"] == "cpu"
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    spec = Spec.load(str(tmp_path))
    dense = {m["name"]: m for m in spec.doc["per_layer"]
             if CELL in m.get("workloads", ())}
    # 18 of its own and `jit_trace_lower_s`, which it shares
    assert len(dense) == 19 and "jit_trace_lower_s" in dense
    from_device = {n for n, m in dense.items()
                   if m["source"] == "device_trace"}
    got = doc["metrics"]
    # all but the three that only a device trace gives; and at this
    # size on the CPU apply may be over before a chunk has landed
    assert set(dense) - from_device - {"chunk_adopt_lag_ms.dense"} \
        <= set(got) <= set(dense) - from_device
    assert got["resolver_miss_share.dense"]["value"] == 0.0
    assert got["device_sig_share.dense"]["value"] >= 100.0
    assert got["prevalidated_hit_share.dense"]["value"] + \
        got["pending_miss_share.dense"]["value"] == pytest.approx(100.0)
    assert ("chunk_adopt_lag_ms.dense" in got) == \
        (got["prevalidated_hit_share.dense"]["value"] > 0.0)
    # every chunk but a batch's last is full: 16 lanes, some padding
    assert 0.0 < got["dispatch_pad_share.dense"]["value"] < 10.0
    assert got["collect_tuples_ms.dense"]["value"] > 0.0
    checks = [ln for ln in lines if ln.startswith("check: ")]
    for what in ("resolver never made", "chunks of the window's batches",
                 "adversarial envelopes (of 10)", "chunks) that differ"):
        assert any(what in ln for ln in checks), what


@pytest.mark.parametrize("control,failing", [
    ("dense.resolver_drops_non_master", "resolver never made"),
    ("dense.chunks_out_of_order", "differ from the oracle"),
])
def test_control_is_not_correct(tmp_path, bucket16, control, failing):
    doc, lines = run(tmp_path, control)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is False
    assert any(failing in ln for ln in failed), failed


def test_real_cell_is_declared_with_its_files():
    spec = Spec.load(R.ROOT)
    wl = spec.workload(REAL)
    assert wl["chips"] == 1
    cfg = spec.config(wl["config"])
    dep = cfg["deployment"]
    assert sum(c["accounts"] for c in dep["classes"].values()) == \
        dep["accounts"] == dep["txs_per_ledger"] == 1000
    per_ledger = sum(c["accounts"] * (c["threshold"] + c["bumped"])
                     for c in dep["classes"].values())
    assert per_ledger == dep["signatures_per_payment_ledger"] == 1416
    multi = sum(c["accounts"] for c in dep["classes"].values()
                if c["extra_signers"])
    assert 100 * multi == dep["multisig_share_percent"] * dep["accounts"]
    # the adversarial envelopes take six accounts of every kind
    rot = dep["rotation"]
    rotated = rot["accounts_per_ledger"] * len(rot["payment_ledgers"])
    assert rotated >= 6 and \
        dep["classes"][rot["class"]]["accounts"] - rotated >= 6
    assert all(c["accounts"] >= 6 for c in dep["classes"].values())
    assert cfg["reduced"] == ["ledgers"] and cfg["what_the_cut_hides"]
    assert set(cfg["assumed"]) >= {"classes", "rotation", "MAX_TX_SET_SIZE"}
    assert spec.traffic(wl["traffic"])["generator"] == "multisig_replay"
    mine = [m for m in spec.doc["per_layer"]
            if REAL in m["workloads"] and m["name"].endswith(".dense")]
    assert len(mine) == 18 and all(m["workloads"] == [REAL] for m in mine)
    assert all(m["moves"] == "catchup_ledgers_per_s" for m in mine)
    shared = [m["name"] for m in spec.doc["per_layer"]
              if REAL in m["workloads"] and m not in mine]
    assert shared == ["jit_trace_lower_s"]
