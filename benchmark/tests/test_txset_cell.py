"""The cell `txset-5000.validate`, rehearsed at tiny size on the CPU as
test_range_cell.py rehearses the range one: the added configuration and
traffic mix lie under `data/added/`, `rehearse.make_root` copies the
files, and this file lays its own entries (`BENCHMARK.add.txset.json`)
over the root that makes. The largest bucket is patched to 16 lanes, so
a set of 52 signatures is three chunks and a remainder. A sound run
comes out correct; under either control of txset_controls.py not
correct."""

import io
import json
import os
import time

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import rehearse as R
from benchmark.tests import txset_controls

CELL = "tiny-txset.tiny-validate"
REAL = "txset-5000.validate"


def make_root(tmp: str) -> str:
    root = R.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(R.ADDED, "BENCHMARK.add.txset.json")) as f:
        add = json.load(f)
    doc["configs"] += add["configs"]
    doc["workloads"] += add["workloads"]
    for m in doc["end_to_end"]:
        more = add["end_to_end_workloads"].get(m["name"])
        if more:
            m["workloads"] = m["workloads"] + more
    # the tiny cell reports every per-layer metric the real one does
    for m in doc["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return root


@pytest.fixture(autouse=True)
def tiny_program(monkeypatch):
    from stellar_core_tpu.ops import chunking
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)


def run(tmp_path, control=None, trace=0):
    out = io.StringIO()
    argv = ["--workload", CELL, "--seed", "4294967339", "--seconds", "2",
            "--trace", str(trace)]
    kw = dict(t0=time.perf_counter(), root=make_root(str(tmp_path)),
              require_chip=False, out=out)
    if control:
        rc = txset_controls.run_under(control, argv, **kw)
    else:
        from benchmark.harness.main import main
        rc = main(argv, **kw)
    assert rc == 0
    lines = out.getvalue().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_traced(tmp_path):
    doc, lines = run(tmp_path, trace=1)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is True and not failed, failed
    # whole ledgers of 52 payments, at least the traffic's least number
    assert doc["failed"] == 0 and doc["attempted"] % 52 == 0 \
        and doc["attempted"] >= 4 * 52
    assert doc["device"]["platform"] == "cpu"
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    assert set(doc["end_to_end_while_traced"]) == {
        "applied_tx_per_s", "close_ms_p90", "setup_s"}
    spec = Spec.load(str(tmp_path))
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if CELL in m.get("workloads", ())}
    from_device = {n for n, m in mine.items()
                   if m["source"] == "device_trace"}
    got = doc["metrics"]
    # every one that needs no device trace is a number: none is silent
    assert set(got) == set(mine) - from_device
    assert all(isinstance(v["value"], float) for v in got.values())
    assert got["device_sig_share.txset"]["value"] >= 100.0
    assert got["txset_cached_share.txset"]["value"] == 0.0
    assert got["host_verify_us_per_tx.txset"]["value"] == 0.0
    # 52 signatures in four runs of 16 lanes
    assert got["dispatch_pad_share.txset"]["value"] == \
        pytest.approx(100.0 * 12 / 64)
    assert 0.0 <= got["txset_validate_wait_ms.txset"]["value"] \
        <= got["txset_validate_ms.txset"]["value"] \
        <= got["received_to_validated_ms.txset"]["value"] + 1.0
    for name in ("scp_self_ms.txset", "dispatch_wall_ms.txset",
                 "apply_us_per_tx.txset", "seal_ms.txset",
                 "complete_wait_ms.txset",
                 "history_tail_us_per_tx.txset"):
        assert got[name]["value"] >= 0.0, name
    checks = [ln for ln in lines if ln.startswith("check: ")]
    for what in ("differs from the publisher's", "dictionary model",
                 "herder.txset.prevalidate.dispatched",
                 "herder.txset.prevalidate.fallback",
                 "off the SCP envelopes' own signatures",
                 "device runs off 4 a set", "supervisor complaints",
                 "without an EXTERNALIZE of its own",
                 "corrupted set (4 of 52", "in order; 12 by the oracle",
                 "programs compiled inside the measured window"):
        assert any(what in ln for ln in checks), what


def test_readers_give_zero_and_not_nothing_at_a_count_of_zero():
    """And nothing on a program without the new zone and counters (the
    parent commit), without raising."""
    from benchmark.harness.cell import Cell
    spec = Spec.load(R.ROOT)
    names = [m["name"] for m in spec.doc["per_layer"]
             if m.get("workloads") == [REAL]]
    cell = Cell(REAL, {}, {}, 1, 30.0, True, "/nonexistent", 1)
    cell.spec = spec
    cell.traffic_counts.update(transactions=5000, signatures=5000,
                               ledgers=1, scp_envelopes=12,
                               envelope_verifies=12)
    for name in names:           # the parent: no such zone, no counter
        assert spec.layer_reader(name)(cell) in (None, 0.0), name
    assert spec.layer_reader("txset_validate_ms.txset")(cell) is None
    assert spec.layer_reader("txset_cached_share.txset")(cell) is None
    cell.zones.update({"herder.txset.validate": (0, 0.0),
                       "crypto.verify.native": (12, 0.0012)})
    cell.counters.update({
        "herder.txset.prevalidate." + k: (0, 0.0)
        for k in ("cached", "dispatched", "fallback")})
    cell.counters.update({"herder.txset.receivedToValidated": (0, 0.0),
                          "crypto.verify.dispatch.wall": (0, 0.0),
                          "crypto.verify.dispatch.padding": (0, 0.0),
                          "crypto.verify.dispatch.batch": (0, 0.0)})
    for name in ("txset_validate_ms.txset", "txset_validate_wait_ms.txset",
                 "received_to_validated_ms.txset",
                 "txset_cached_share.txset", "host_verify_us_per_tx.txset",
                 "dispatch_wall_ms.txset", "dispatch_pad_share.txset",
                 "device_sig_share.txset"):
        assert spec.layer_reader(name)(cell) == 0.0, name
    # one native verify beyond the envelopes' own is read
    cell.zones["crypto.verify.native"] = (13, 0.0013)
    assert spec.layer_reader("host_verify_us_per_tx.txset")(cell) == \
        pytest.approx(0.0001 / 5000 * 1e6)


@pytest.mark.parametrize("control,by", [
    ("txset.prevalidator_says_true",
     ["corrupted set (4 of 52", "flipped signatures the device called"]),
    ("txset.cache_left_warm",
     ["herder.txset.prevalidate.dispatched", "device runs off 4 a set"])])
def test_control_is_not_correct(tmp_path, control, by):
    doc, lines = run(tmp_path, control)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is False
    for what in by:
        assert any(what in ln for ln in failed), (what, failed)
    # the chain and the accounts are still the publisher's
    assert not any("differs from the publisher's" in ln
                   or "dictionary model" in ln for ln in failed)


def test_real_cell_is_declared_with_its_files():
    spec = Spec.load(R.ROOT)
    wl = spec.workload(REAL)
    assert wl["chips"] == 1 and wl["traffic"] == "validate"
    cfg = spec.config(wl["config"])
    dep = cfg["deployment"]
    assert dep["accounts"] == dep["txs_per_ledger"] \
        == dep["signatures_per_ledger"] == 5000
    assert (dep["validators"], dep["threshold"]) == (3, 2)
    assert cfg["node"]["QUORUM_SET"] == {"THRESHOLD": 2}
    assert cfg["node"]["NODE_IS_VALIDATOR"] is True
    assert cfg["node"]["SIGNATURE_VERIFY_BACKEND"] == "tpu"
    assert "MANUAL_CLOSE" not in cfg["node"]
    assert "VERIFY_DISPATCH_DEADLINE_MS" not in cfg["node"]
    assert cfg["publisher_overrides"]["SIGNATURE_VERIFY_BACKEND"] == "native"
    assert cfg["reduced"] == []
    said = " ".join(cfg["guarantees"])
    for held in ("fully validated", "two of the three", "committed",
                 "equals the publisher's", "bad signature"):
        assert held in said, held
    assert next(iter(cfg["assumed"])) == "cached_share"
    assert cfg["what_the_sources_bear_out"] and cfg["what_the_cut_hides"]
    entry = next(c for c in spec.doc["configs"] if c["name"] == wl["config"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    traffic = spec.traffic(wl["traffic"])
    assert traffic["generator"] == "txset_follow"
    p = traffic["params"]
    assert "cached_share" not in p and p["corrupted"] == 24
    assert 12 <= p["min_ledgers"] <= 24 <= p["recorded_ledgers"]
    # no checkpoint ledger: 2, 3, then the recorded payment ledgers
    assert 3 + p["recorded_ledgers"] < 63
    mine = [m for m in spec.doc["per_layer"]
            if REAL in m["workloads"] and m["name"].endswith(".txset")]
    assert mine and all(m["workloads"] == [REAL] for m in mine)
    assert {m["moves"] for m in mine} == {"close_ms_p90",
                                          "applied_tx_per_s"}
    shared = [m["name"] for m in spec.doc["per_layer"]
              if REAL in m["workloads"] and m not in mine]
    assert "jit_trace_lower_s" in shared
    reports = [m["name"] for m in spec.metrics_for("end_to_end", REAL, [])]
    assert {"applied_tx_per_s", "close_ms_p90", "setup_s"} <= set(reports)
    assert "submit_applied_ms_p95" not in reports
    for m in spec.doc["end_to_end"]:
        if m["name"] in ("applied_tx_per_s", "close_ms_p90"):
            assert REAL in m["workloads"]
    for e in spec.doc["configs"] + spec.doc["workloads"]:
        assert len(e["why"]) <= 200 and len(e.get("source", "")) <= 200
