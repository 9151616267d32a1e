"""The cell `txset-5000-flood.flooded`, rehearsed at tiny size on the CPU
as test_txset_cell.py rehearses the followed one: the added
configuration and traffic mix lie under `data/added/`,
`rehearse.make_root` copies the files, and this file lays its own
entries (`BENCHMARK.add.flood.json`) over the root that makes. The
largest bucket is patched to 16 lanes and the twin's verify service
flushes at 16, so a node loads one 16-lane shape when it starts; a
ledger of 48 payments arrives in three bursts of 16. A sound run comes
out correct; under each control of flood_controls.py not correct."""

import io
import json
import os
import time

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import flood_controls
from benchmark.tests import rehearse as R

CELL = "tiny-flood.tiny-flooded"
REAL = "txset-5000-flood.flooded"


def make_root(tmp: str) -> str:
    root = R.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(R.ADDED, "BENCHMARK.add.flood.json")) as f:
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
    from stellar_core_tpu.main import application
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.ops import chunking, verifier
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)
    # on the CPU backend a node loads its shapes only where asked
    monkeypatch.setattr(application, "SHAPE_LOADING_BACKENDS",
                        ("tpu", "cpu"))
    # every run is its own process on the chip: what an earlier test
    # of this process ran is not this node's
    monkeypatch.setattr(verifier, "_SHAPES_RUN", set())
    # a control replaces it and leaves it so
    monkeypatch.setattr(Application, "_load_verify_shapes",
                        Application._load_verify_shapes)


def run(tmp_path, control=None, trace=0):
    out = io.StringIO()
    argv = ["--workload", CELL, "--seed", "4294967339", "--seconds", "2",
            "--trace", str(trace)]
    kw = dict(t0=time.perf_counter(), root=make_root(str(tmp_path)),
              require_chip=False, out=out)
    if control:
        rc = flood_controls.run_under(control, argv, **kw)
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
    # whole ledgers of 48 payments, at least the traffic's least number
    assert doc["failed"] == 0 and doc["attempted"] % 48 == 0 \
        and doc["attempted"] >= 4 * 48
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
    assert got["flood_device_sig_share.flood"]["value"] == 100.0
    assert got["txset_cached_share.flood"]["value"] == 100.0
    assert got["shape_missed.flood"]["value"] == 0.0
    assert got["host_verify_us_per_tx.flood"]["value"] == 0.0
    # bursts of 16 in a 16-lane shape
    assert got["dispatch_pad_share.flood"]["value"] == 0.0
    assert 0.0 < got["verify_service_native_share.flood"]["value"] < 100.0
    assert 1.0 < got["flood_batch_occupancy.flood"]["value"] < 16.0
    assert 0.0 < got["flood_verify_wait_us_per_tx.flood"]["value"] \
        <= got["flood_admit_us_per_tx.flood"]["value"]
    assert 0.0 < got["txset_validate_ms.flood"]["value"]
    for name in ("queue_upkeep_ms.flood", "scp_self_ms.flood",
                 "root_point_reads_per_tx.flood", "dispatch_wall_ms.flood",
                 "apply_us_per_tx.flood", "seal_ms.flood"):
        assert got[name]["value"] >= 0.0, name
    checks = [ln for ln in lines if ln.startswith("check: ")]
    for what in ("differs from the publisher's", "dictionary model",
                 "differs from flood_model's",
                 "the node's queue held a frame",
                 "sent to the device at admission",
                 "(3 bursts a ledger)",
                 "crypto.verify_service.flush.native",
                 "crypto.verify_service.fallback",
                 "while the verify cache still had room",
                 "herder.txset.prevalidate.fallback",
                 "off the SCP envelopes' own signatures",
                 "supervisor complaints",
                 "crypto.verify.shape.loaded", "crypto.verify.shape.missed",
                 "traces, lowerings and compiles inside the window",
                 "without an EXTERNALIZE of its own",
                 "adversarial burst (16 frames, 3 signatures flipped, 2 "
                 "already pending)", "the oracle's verdicts, in order",
                 "signatures sent off 14",
                 "herder.flood.received / .admitted / .duplicate / .badSig",
                 "with 10 of its 48 transactions withheld",
                 "programs compiled inside the measured window"):
        assert any(what in ln for ln in checks), what


def test_readers_give_zero_and_not_nothing_at_a_count_of_zero():
    """The four that must, and nothing on a program without the new
    zones and counters (the parent commit), without raising."""
    from benchmark.harness.cell import Cell
    spec = Spec.load(R.ROOT)
    names = [m["name"] for m in spec.doc["per_layer"]
             if m.get("workloads") == [REAL]]
    assert len(names) == len(set(names)) and names
    cell = Cell(REAL, {}, {}, 1, 30.0, True, "/nonexistent", 1)
    cell.spec = spec
    cell.traffic_counts.update(transactions=0, signatures=0, flooded=0,
                               bursts=0, ledgers=1, scp_envelopes=0,
                               envelope_verifies=0)
    for name in names:           # the parent: no such zone, no counter
        assert spec.layer_reader(name)(cell) in (None, 0.0), name
    daggered = ("flood_device_sig_share.flood",
                "verify_service_native_share.flood", "shape_missed.flood",
                "root_point_reads_per_tx.flood")
    for name in daggered + ("flood_admit_us_per_tx.flood",
                            "flood_verify_wait_us_per_tx.flood"):
        assert spec.layer_reader(name)(cell) is None, name
    cell.counters.update({
        "herder.flood.received": (0, 0.0),
        "crypto.verify_service.flush.native": (0, 0.0),
        "crypto.verify.shape.missed": (0, 0.0),
        "ledger.root.point.sql": (0, 0.0)})
    cell.zones.update({"herder.recvTransactions": (0, 0.0),
                       "herder.recvTransactions.verify": (0, 0.0)})
    for name in daggered + ("flood_admit_us_per_tx.flood",
                            "flood_verify_wait_us_per_tx.flood"):
        assert spec.layer_reader(name)(cell) == 0.0, name
    # and what they read where there is something
    cell.traffic_counts.update(transactions=5000, flooded=5000)
    cell.counters.update({
        "herder.flood.received": (5000, 0.0),
        "herder.flood.admitted": (5000, 0.0),
        "crypto.verify.dispatch.batch": (26, 5190.0),
        "herder.txset.prevalidate.dispatched": (190, 0.0),
        "crypto.verify_service.occupancy": (37, 5012.0),
        "crypto.verify_service.flush.native": (12, 0.0),
        "crypto.verify.shape.missed": (2, 0.0),
        "ledger.root.point.sql": (7500, 0.0)})
    cell.zones.update({"herder.recvTransactions": (25, 0.5),
                       "herder.recvTransactions.verify": (25, 0.125),
                       "herder.ledgerClosed": (1, 0.03)})
    read = {n: spec.layer_reader(n)(cell) for n in names}
    assert read["flood_device_sig_share.flood"] == 100.0
    assert read["verify_service_native_share.flood"] == \
        pytest.approx(100.0 * 12 / 37)
    assert read["flood_batch_occupancy.flood"] == pytest.approx(5012 / 37)
    assert read["shape_missed.flood"] == 2.0
    assert read["root_point_reads_per_tx.flood"] == 1.5
    assert read["flood_admit_us_per_tx.flood"] == pytest.approx(100.0)
    assert read["flood_verify_wait_us_per_tx.flood"] == pytest.approx(25.0)
    assert read["queue_upkeep_ms.flood"] == pytest.approx(30.0)


@pytest.mark.parametrize("control,by,sound", [
    ("flood.service_says_true",
     ["differ from flood_model's", "the oracle's verdicts, in order",
      "herder.flood.received"],
     ["differs from flood_model's, or is not ADD_STATUS_PENDING"]),
    ("flood.skipped",
     ["frames flooded inside the window",
      "herder.txset.prevalidate.cached 0",
      "while the verify cache still had room"], []),
    ("flood.no_start_up_load",
     ["crypto.verify.shape.loaded", "crypto.verify.shape.missed"],
     ["differs from flood_model's", "adversarial burst"])])
def test_control_is_not_correct(tmp_path, control, by, sound):
    doc, lines = run(tmp_path, control)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is False
    for what in by:
        assert any(what in ln for ln in failed), (what, failed)
    for what in sound:
        assert not any(what in ln for ln in failed), (what, failed)
    # the chain and the accounts are still the publisher's
    assert not any("differs from the publisher's" in ln
                   or "dictionary model" in ln for ln in failed)


def test_real_cell_is_declared_with_its_files():
    spec = Spec.load(R.ROOT)
    wl = spec.workload(REAL)
    assert wl["chips"] == 1 and wl["traffic"] == "flooded"
    cfg = spec.config(wl["config"])
    cold = spec.config("txset-5000")
    dep = cfg["deployment"]
    # copied and not cut from txset-5000
    for key in ("validators", "threshold", "accounts", "txs_per_ledger",
                "signatures_per_ledger", "starting_balance"):
        assert dep[key] == cold["deployment"][key], key
    assert cfg["publisher_overrides"] == cold["publisher_overrides"]
    assert cfg["guarantees"][:len(cold["guarantees"])] == cold["guarantees"]
    assert len(cfg["guarantees"]) == len(cold["guarantees"]) + 3
    added = {k: v for k, v in cfg["node"].items()
             if cold["node"].get(k) != v}
    assert added == {"FLOOD_TX_PERIOD_MS": 200,
                     "FLOOD_OP_RATE_PER_LEDGER": 1.0}
    assert set(cold["node"]) <= set(cfg["node"])
    assert dep["flood"] == {"period_ms": 200, "op_rate_per_ledger": 1.0,
                            "reading_capacity": 200, "burst_txs": 200,
                            "bursts_per_ledger": 25, "mode": "pull"}
    # a peer's budget of one period is the burst
    assert dep["flood"]["op_rate_per_ledger"] * dep["txs_per_ledger"] \
        * dep["flood"]["period_ms"] / 5000 == dep["flood"]["burst_txs"] \
        == dep["txs_per_ledger"] / dep["flood"]["bursts_per_ledger"]
    assert cfg["reduced"] == []
    assert next(iter(cfg["assumed"])) == "flooded_share"
    assert cfg["what_the_sources_bear_out"] and cfg["what_the_cut_hides"]
    entry = next(c for c in spec.doc["configs"] if c["name"] == wl["config"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] != next(c for c in spec.doc["configs"]
                                 if c["name"] == "txset-5000")["file"]
    traffic = spec.traffic(wl["traffic"])
    assert traffic["generator"] == "txset_flood"
    p = traffic["params"]
    assert p["burst_txs"] == dep["flood"]["burst_txs"]
    assert (p["corrupted"], p["duplicates"], p["withheld"]) == (24, 8, 600)
    assert 12 <= p["min_ledgers"] <= 24 == p["recorded_ledgers"]
    # a warm ledger, the window's, and one for the checks
    assert p["min_ledgers"] + 2 <= p["recorded_ledgers"]
    assert 3 + p["recorded_ledgers"] < 63
    mine = [m for m in spec.doc["per_layer"]
            if REAL in m["workloads"] and m["name"].endswith(".flood")]
    assert mine and all(m["workloads"] == [REAL] for m in mine)
    assert {m["moves"] for m in mine} == {"close_ms_p90",
                                          "applied_tx_per_s"}
    for m in mine:
        assert os.path.exists(os.path.join(
            R.BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    shared = [m["name"] for m in spec.doc["per_layer"]
              if REAL in m["workloads"] and m not in mine]
    assert shared == ["jit_trace_lower_s"]
    reports = [m["name"] for m in spec.metrics_for("end_to_end", REAL, [])]
    assert set(reports) == {"applied_tx_per_s", "close_ms_p90", "setup_s"}
    for e in spec.doc["configs"] + spec.doc["workloads"]:
        assert len(e["why"]) <= 200 and len(e.get("source", "")) <= 200
