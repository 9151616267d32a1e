"""Mesh observatory tests (ISSUE 8): hash-keyed propagation tracking,
SCP slot timelines, multi-node trace merge with flow stitching, the
clusterstatus route, and the observability satellites (stamp-map
bounds, clearmetrics clean-slate, trace_report cluster modes)."""

import json
import os
import sys

import pytest

from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.overlay.propagation import PropagationTracker
from stellar_core_tpu.simulation import LoadGenerator, topologies
from stellar_core_tpu.util import tracing
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock

import test_overlay as ovl

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))

import trace_report                                        # noqa: E402


@pytest.fixture(autouse=True)
def _no_leftover_tracing():
    yield
    with tracing._state_lock:
        tracing._active_count = 0
        tracing.ENABLED = False


# ------------------------------------------------ merged cluster trace --

@pytest.fixture(scope="module")
def merged_trace_doc():
    """One traced 4-node run shared by the merge/flow/slot/report
    tests: accounts + payments over real SCP, every node recording,
    merged through Simulation.merged_trace."""
    sim = topologies.core(4)
    try:
        sim.start_tracing()
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(2))
        app = sim.apps()[0]
        lg = LoadGenerator(app)
        assert lg.generate_accounts(4) == 4
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        lg.sync_account_seqs()
        assert lg.generate_payments(4) == 4
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        assert lg.failed == 0
        doc = sim.merged_trace()
        flood = app.command_handler.handle(
            "peers")["authenticated_peers"]["flood"]
        cluster = [a.command_handler.handle("clusterstatus")
                   for a in sim.apps()]
        timelines = dict(app.herder.slot_timelines)
    finally:
        sim.stop_all_nodes()
    return {"doc": doc, "flood": flood, "cluster": cluster,
            "timelines": timelines}


def test_merged_trace_has_one_process_lane_per_node(merged_trace_doc):
    doc = merged_trace_doc["doc"]
    events = json.loads(json.dumps(doc))["traceEvents"]   # serializable
    pids = {e["pid"] for e in events if e.get("ph") != "M"}
    assert len(pids) == 4
    # every lane carries process_name metadata with the node label
    named = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert pids <= set(named)
    assert len(set(named.values())) == 4          # distinct labels
    # per-node async ids are label-scoped so tracks never merge
    for e in events:
        if e.get("ph") in ("b", "e"):
            assert ":" in e["id"], e


def test_flow_events_follow_tx_across_three_lanes(merged_trace_doc):
    """Acceptance: a single tx hash can be followed send→recv across
    ≥3 node lanes via flow events."""
    events = merged_trace_doc["doc"]["traceEvents"]
    # pick a tx hash that is ALSO on the submit node's e2e track
    e2e_ids = {e["id"].split(":", 1)[1] for e in events
               if e.get("ph") in ("b", "e") and e["name"] == "tx.e2e"}
    assert e2e_ids
    by_hash = {}
    for e in events:
        if e.get("ph") == "i" and e.get("name") in ("flood.send",
                                                    "flood.recv"):
            args = e.get("args") or {}
            if args.get("type") == "TRANSACTION":
                by_hash.setdefault(args["hash"], []).append(e)
    followed = [h for h, evs in by_hash.items()
                if h in e2e_ids and len({e["pid"] for e in evs}) >= 3]
    assert followed, "no tx hash observable on >=3 node lanes"
    h = followed[0]
    flows = sorted((e for e in events if e.get("ph") in ("s", "t", "f")
                    and e.get("id") == h), key=lambda e: e["ts"])
    assert flows, "no flow chain for the followed tx"
    assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
    assert all(e["ph"] == "t" for e in flows[1:-1])
    assert len({e["pid"] for e in flows}) >= 3
    # the chain strictly advances in time
    ts = [e["ts"] for e in flows]
    assert all(b > a for a, b in zip(ts, ts[1:]))
    # and connects a send to a recv: the first endpoint is the origin's
    # send, a later one is a different node's recv
    send_pids = {e["pid"] for e in by_hash[h]
                 if e["name"] == "flood.send"}
    recv_pids = {e["pid"] for e in by_hash[h]
                 if e["name"] == "flood.recv"}
    assert flows[0]["pid"] in send_pids
    assert recv_pids - send_pids


def test_slot_phase_spans_strictly_ordered_per_node(merged_trace_doc):
    events = merged_trace_doc["doc"]["traceEvents"]
    begins = {}
    for e in events:
        if e.get("ph") == "b" and e["name"].startswith("scp.slot."):
            phase = e["name"].rsplit(".", 1)[1]
            slot = e["args"]["slot"]
            begins.setdefault((e["pid"], slot), {})[phase] = e["ts"]
    assert begins, "no slot phase spans recorded"
    complete = 0
    for (pid, slot), phases in begins.items():
        if {"nominate", "prepare", "confirm"} <= set(phases):
            complete += 1
            assert phases["nominate"] <= phases["prepare"] \
                <= phases["confirm"], (pid, slot, phases)
    assert complete >= 4, "no node recorded a full phase progression"
    # herder-side timeline bounded and phase-ordered too
    for slot, tl in merged_trace_doc["timelines"].items():
        keys = [k for k in ("nominate", "prepare", "confirm",
                            "externalize") if k in tl]
        vals = [tl[k] for k in keys]
        assert vals == sorted(vals), (slot, tl)


def test_trace_report_slots_and_flood_modes(merged_trace_doc, tmp_path,
                                            capsys):
    """Acceptance: --slots and --flood each render a non-empty report
    from a merged multinode trace."""
    path = str(tmp_path / "merged.json")
    with open(path, "w") as f:
        json.dump(merged_trace_doc["doc"], f)
    rows = trace_report.report_slots(path)
    out = capsys.readouterr().out
    assert rows and "slot timelines" in out
    assert any(r["slowest"] for r in rows)
    summary = trace_report.report_flood(path)
    out = capsys.readouterr().out
    assert summary["messages"] > 0 and "hop-count" in out
    assert summary["recvs"] > summary["messages"]     # flood redundancy
    assert summary["duplicates"] > 0
    assert summary["links"], "no per-link latency measured"
    assert max(int(k) for k in summary["hop_histogram"]) >= 3


def test_duplicate_accounting_and_peers_route(merged_trace_doc):
    flood = merged_trace_doc["flood"]
    # a 4-node complete graph re-floods everything: duplicates certain
    assert flood["unique"] > 0 and flood["duplicates"] > 0
    assert flood["duplicate_ratio"] > 0
    assert flood["redundancy"] > 1.0


def test_clusterstatus_valid_for_every_node(merged_trace_doc):
    cluster = merged_trace_doc["cluster"]
    assert len(cluster) == 4
    for doc in cluster:
        json.dumps(doc)                              # valid JSON
        cs = doc["clusterstatus"]
        assert cs["node"] and cs["label"]
        assert cs["ledger"]["num"] >= 2 and cs["ledger"]["hash"]
        assert cs["close"]["count"] >= 2
        assert cs["flood"]["unique"] > 0
        assert cs["peers"]["authenticated"] == 3
        assert isinstance(cs["healthy"], bool)
        assert cs["slot_phases"]["nominate"]["count"] > 0
        assert cs["herder_state"]


# -------------------------------------------------- propagation bounds --

def test_stamp_map_bounded_and_dropped_counted():
    """Satellite: a never-externalized tx cannot grow the stamp map —
    TTL prune past the threshold, evictions counted in
    tracing.stamps.dropped (the ledger.transaction.e2e policy)."""
    m = MetricsRegistry()
    tr = PropagationTracker(metrics=m)
    tr.PRUNE_THRESHOLD = 100
    # a flood of never-externalized hashes at t=0
    for i in range(150):
        tr.on_recv(b"%032d" % i, now=0.0)
    assert len(tr) == 150          # inside the TTL nothing is dropped
    # one more arrival past the TTL prunes the stale backlog
    tr.on_recv(b"fresh" + b"\x00" * 27,
               now=tr.STAMP_TTL_SECONDS + 1.0)
    assert len(tr) <= tr.PRUNE_THRESHOLD
    dropped = m.to_json()["tracing.stamps.dropped"]["count"]
    assert dropped >= 150 - tr.PRUNE_THRESHOLD
    # externalize stamps are update-only: unseen hashes add nothing
    before = len(tr)
    tr.on_externalized(b"never-seen" + b"\x00" * 22)
    assert len(tr) == before


def test_propagation_duplicate_detection():
    tr = PropagationTracker()
    h = b"\x01" * 32
    assert tr.on_recv(h, now=1.0) is False      # first delivery
    assert tr.on_recv(h, now=2.0) is True       # redundant
    assert tr.on_recv(h, duplicate=False, now=3.0) is False  # override
    # a locally-admitted tx makes a later delivery a duplicate
    h2 = b"\x02" * 32
    tr.on_admitted(h2, now=1.0)
    assert tr.on_recv(h2, now=2.0) is True
    rep = tr.report()
    assert rep["unique"] == 2 and rep["duplicates"] == 2
    assert rep["redundancy"] == 2.0
    tr.clear()
    assert len(tr) == 0 and tr.report()["unique"] == 0


# ---------------------------------------------------- clearmetrics reset --

def test_clearmetrics_resets_peer_counters_and_stamp_dicts():
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        app = apps[0]
        peer = (app.overlay_manager.get_authenticated_peers())[0]
        peer.duplicate_messages = 7
        assert peer.messages_read > 0 and peer.bytes_written > 0
        app.propagation.on_recv(b"\x03" * 32)
        app.herder._tx_submit_times[b"\x04" * 32] = 1.0
        app.herder.slot_timelines[5] = {"nominate": 1.0}
        assert app.command_handler.handle(
            "clearmetrics")["status"] == "ok"
        assert peer.messages_read == 0 and peer.messages_written == 0
        assert peer.bytes_read == 0 and peer.bytes_written == 0
        assert peer.duplicate_messages == 0
        assert len(app.propagation) == 0
        assert app.herder._tx_submit_times == {}
        assert app.herder.slot_timelines == {}
        # flood counters reset via the registry clear
        assert app.metrics.to_json()[
            "overlay.flood.unique"]["count"] == 0
    finally:
        ovl.shutdown(apps)


def test_clusterstatus_on_bare_node():
    """The route answers on a standalone node too (no overlay peers,
    no SCP slots yet) — the multi-process harness must be able to poll
    it from boot."""
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        doc = app.command_handler.handle("clusterstatus")
        json.dumps(doc)
        cs = doc["clusterstatus"]
        assert cs["ledger"]["num"] >= 1
        assert cs["close"] == {"count": 0} or cs["close"]["count"] >= 0
        assert cs["peers"]["authenticated"] == 0
        assert cs["healthy"] is True
        app.manual_close()
        cs = app.command_handler.handle("clusterstatus")[
            "clusterstatus"]
        assert cs["close"]["count"] >= 1
        assert cs["close"]["p99_ms"] >= cs["close"]["median_ms"] >= 0
    finally:
        app.shutdown()
