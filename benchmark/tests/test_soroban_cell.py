"""The cell `soroban-auth.auth-replay`, rehearsed at tiny size on the CPU
as test_txset_cell.py rehearses the followed one: the added
configuration and traffic mix lie under `data/added/`,
`rehearse.make_root` copies the files, and this file lays its own
entries (`BENCHMARK.add.soroban.json`) over the root that makes. The
largest bucket is patched to 16 lanes, so a checkpoint of 218 tuples is
thirteen chunks and a remainder. Every replay's batch is settled at its
dispatch (`soroban_controls.settled`): a 16-lane program on the CPU is
slower than apply, and a table that has answered nothing shows neither
the mechanism nor its controls. A sound run comes out correct; under
either control of soroban_controls.py not correct."""

import io
import json
import os
import time

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import rehearse as R
from benchmark.tests import soroban_controls

CELL = "tiny-soroban.tiny-auth-replay"
REAL = "soroban-auth.auth-replay"


def make_root(tmp: str) -> str:
    root = R.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(R.ADDED, "BENCHMARK.add.soroban.json")) as f:
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
    from stellar_core_tpu.tx import frame
    monkeypatch.setattr(chunking, "MAX_BUCKET", 16)
    # a control replaces it and leaves it so
    monkeypatch.setattr(frame, "ApplyContext", frame.ApplyContext)


def run(tmp_path, control=None, trace=0):
    out = io.StringIO()
    argv = ["--workload", CELL, "--seed", "4294967339", "--seconds", "2",
            "--trace", str(trace)]

    def hook(driver):
        soroban_controls.settled(driver)
        if control:
            soroban_controls.CONTROLS[control](driver)
    from benchmark.harness.main import main
    rc = main(argv, t0=time.perf_counter(), root=make_root(str(tmp_path)),
              require_chip=False, out=out, driver_hook=hook)
    assert rc == 0
    lines = out.getvalue().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_traced(tmp_path):
    doc, lines = run(tmp_path, trace=1)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is True and not failed, failed
    # whole replays of ledgers 2..63
    assert doc["failed"] == 0 and doc["attempted"] % 62 == 0 \
        and doc["attempted"] >= 62
    assert doc["device"]["platform"] == "cpu"
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    assert set(doc["end_to_end_while_traced"]) == {
        "catchup_ledgers_per_s", "setup_s"}
    spec = Spec.load(str(tmp_path))
    mine = {m["name"]: m for m in spec.doc["per_layer"]
            if CELL in m.get("workloads", ())}
    from_device = {n for n, m in mine.items()
                   if m["source"] == "device_trace"}
    got = doc["metrics"]
    # every one that needs no device trace is a number: none is silent
    assert set(got) == set(mine) - from_device
    assert all(isinstance(v["value"], float) for v in got.values())
    assert got["auth_prevalidated_share.auth"]["value"] == 100.0
    assert got["prevalidated_hit_share.auth"]["value"] == 100.0
    assert got["device_sig_share.auth"]["value"] >= 100.0
    assert got["resolver_miss_share.auth"]["value"] == 0.0
    assert got["host_verify_us_per_tx.auth"]["value"] == 0.0
    # 96 auth tuples of 218: 3 ledgers of 32, 11 of set-up's own
    assert got["auth_tuple_share.auth"]["value"] == \
        pytest.approx(100.0 * 96 / 218)
    # 218 tuples in fourteen runs of 16 lanes
    assert got["dispatch_pad_share.auth"]["value"] == \
        pytest.approx(100.0 * 6 / 224)
    assert 0.0 < got["soroban_auth_us_per_tx.auth"]["value"]
    assert 0.0 < got["soroban_invoke_us_per_tx.auth"]["value"] \
        <= got["apply_us_per_tx.auth"]["value"]
    checks = [ln for ln in lines if ln.startswith("check: ")]
    for what in ("differs from the publisher's", "dictionary model",
                 "bit-flipped auth tuples a replay",
                 "archived results (of 120 transfers, 12 failed",
                 "nonce entries", "off crypto.collect.auth",
                 "soroban.auth.verify.prevalidated + .fallback (90 + 0)",
                 "soroban.auth.verify.fallback) beyond",
                 "crypto.verify.native) beyond",
                 "crypto.prevalidated.miss.unknown",
                 "chunks of the window's batches", "supervisor complaints",
                 "3 bit-flipped and 4 sound auth tuples and 210 of the archive",
                 "programs compiled inside the measured window"):
        assert any(what in ln for ln in checks), what


def test_readers_give_zero_and_not_nothing_at_a_count_of_zero():
    """And nothing on a program without the new zones and counters (the
    parent commit), without raising."""
    from benchmark.harness.cell import Cell
    spec = Spec.load(R.ROOT)
    new = ("soroban_invoke_us_per_tx.auth", "soroban_auth_us_per_tx.auth",
           "auth_prevalidated_share.auth", "auth_tuple_share.auth")
    cell = Cell(REAL, {}, {}, 1, 30.0, True, "/nonexistent", 1)
    cell.spec = spec
    cell.traffic_counts.update(transactions=59011, signatures=106211,
                               ledgers=62)
    for name in new:             # the parent: no such zone, no counter
        assert spec.layer_reader(name)(cell) is None, name
    assert spec.layer_reader("host_verify_us_per_tx.auth")(cell) == 0.0
    # (no `soroban.auth` at all where no entry carried address
    # credentials: the program reports a zone from its first hit on)
    cell.zones.update({"soroban.invoke": (0, 0.0)})
    cell.counters.update({
        name: (0, 0.0) for name in (
            "soroban.auth.verify.prevalidated",
            "soroban.auth.verify.fallback", "soroban.auth.entries.address",
            "crypto.collect.auth", "crypto.collect.candidates")})
    for name in new:
        assert spec.layer_reader(name)(cell) == 0.0, name
    cell.zones.update({"soroban.invoke": (1000, 0.3),
                       "soroban.auth": (800, 0.12)})
    cell.counters.update({
        "soroban.auth.verify.prevalidated": (796, 0.0),
        "soroban.auth.verify.fallback": (4, 0.0),
        "soroban.auth.entries.address": (800, 0.0),
        "crypto.collect.auth": (800, 0.0),
        "crypto.collect.candidates": (1800, 0.0)})
    read = {name: spec.layer_reader(name)(cell) for name in new}
    assert read == pytest.approx({
        "soroban_invoke_us_per_tx.auth": 300.0,
        "soroban_auth_us_per_tx.auth": 150.0,
        "auth_prevalidated_share.auth": 99.5,
        "auth_tuple_share.auth": 100.0 * 800 / 1800})


@pytest.mark.parametrize("control,by,held", [
    ("soroban.host_never_sees_the_table",
     ["soroban.auth.verify.fallback) beyond",
      "crypto.verify.native) beyond"],
     ["differs from the publisher's", "dictionary model", "nonce entries"]),
    ("soroban.table_says_true",
     ["replays that ended other than WORK_SUCCESS",
      "differs from the publisher's"], [])])
def test_control_is_not_correct(tmp_path, control, by, held):
    doc, lines = run(tmp_path, control)
    failed = [ln for ln in lines if "FAILED" in ln]
    assert doc["correct"] is False
    for what in by:
        assert any(what in ln for ln in failed), (what, failed)
    # what the control leaves alone is still the publisher's
    for what in held:
        assert not any(what in ln for ln in failed), (what, failed)


def test_real_cell_is_declared_with_its_files():
    spec = Spec.load(R.ROOT)
    wl = spec.workload(REAL)
    assert wl["chips"] == 1 and wl["traffic"] == "auth-replay"
    cfg = spec.config(wl["config"])
    dep = cfg["deployment"]
    assert dep["accounts"] == dep["txs_per_ledger"] == 1000
    assert dep["relayed_per_ledger"] == 800 \
        and dep["relayed_share_percent"] == 80
    assert dep["signatures_per_transfer_ledger"] == 1800
    assert (dep["checkpoint"], dep["payment_ledgers"]) == (63, 59)
    assert dep["adversarial_per_kind"] * len(dep["adversarial_kinds"]) == 8
    assert cfg["node"]["SIGNATURE_VERIFY_BACKEND"] == "tpu"
    assert cfg["node"]["TESTING_SOROBAN_HIGH_LIMIT_OVERRIDE"] is True
    assert cfg["publisher_overrides"]["SIGNATURE_VERIFY_BACKEND"] == "native"
    assert cfg["reduced"] == ["ledgers"] and "ledgers" in cfg["reduced_why"]
    said = " ".join(cfg["guarantees"])
    for held in ("byte-identical", "txSetResultHash", "auth-entry signature",
                 "this nonce", "still charges the fee", "nonce entries"):
        assert held in said, held
    assert next(iter(cfg["assumed"])) == "relayed_share"
    assert cfg["what_the_sources_bear_out"] and cfg["what_the_cut_hides"]
    entry = next(c for c in spec.doc["configs"] if c["name"] == wl["config"])
    assert entry["source"] == cfg["source"] \
        and entry["reduced"] == cfg["reduced"]
    traffic = spec.traffic(wl["traffic"])
    assert traffic["generator"] == "soroban_replay"
    mine = [m for m in spec.doc["per_layer"]
            if REAL in m["workloads"] and m["name"].endswith(".auth")]
    assert mine and all(m["workloads"] == [REAL] for m in mine)
    assert {m["moves"] for m in mine} == {"catchup_ledgers_per_s"}
    for m in mine:
        assert os.path.exists(os.path.join(
            spec.dir, "layer_metrics", m["name"] + ".py")), m["name"]
    shared = [m["name"] for m in spec.doc["per_layer"]
              if REAL in m["workloads"] and m not in mine]
    assert shared == ["jit_trace_lower_s"]
    reports = [m["name"] for m in spec.metrics_for("end_to_end", REAL, [])]
    assert set(reports) == {"catchup_ledgers_per_s", "setup_s"}
    for e in spec.doc["configs"] + spec.doc["workloads"]:
        assert len(e["why"]) <= 200 and len(e.get("source", "")) <= 200
