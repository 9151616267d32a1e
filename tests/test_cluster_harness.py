"""Multi-process cluster harness (ISSUE 9): real node processes, real
TCP, chaos and verdicts over HTTP.

Tier-1 legs: config-rendering round trips, the multi-process trace
merge, a 1-node port-file/SIGTERM/restart lifecycle, and the 3-process
smoke (spawn on ephemeral ports, converge over real sockets,
clusterstatus_ok everywhere, raw `tx`-route submission, clean
teardown). The ≥9-node tiered chaos leg (bad-sig flood over the chaos
route + kill -9 churn with catchup over the wire) is marked `slow`.
"""

import base64
import json
import os
import time

import pytest

from stellar_core_tpu.main.config import Config
from stellar_core_tpu.simulation.cluster import (Cluster,
                                                 run_cluster_scenario)
from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.util import chaos

pytestmark = pytest.mark.cluster


# ------------------------------------------------------------- unit legs --
def test_config_rendering_round_trips(tmp_path):
    """Every rendered TOML must load back through Config.load into the
    identity/quorum/storage shape the node process will actually run."""
    c = Cluster(3, 3, str(tmp_path))
    assert len(c.nodes) == 9
    assert len({n.peer_port for n in c.nodes}) == 9      # unique ports
    assert len({n.data_dir for n in c.nodes}) == 9
    for node in c.nodes:
        cfg = Config.load(node.cfg_path)
        assert cfg.NODE_SEED.public_key().raw == node.node_id
        assert cfg.NODE_IS_VALIDATOR and cfg.FORCE_SCP
        assert cfg.HTTP_PORT == 0                        # ephemeral
        assert cfg.PEER_PORT == node.peer_port
        assert cfg.ALLOW_CHAOS_INJECTION                 # harness-only
        assert cfg.DATABASE.startswith("sqlite3://")
        assert node.data_dir in cfg.DATABASE
        assert node.data_dir in cfg.BUCKET_DIR_PATH
        # the tiered quorum structure survives the TOML round trip
        assert cfg.QUORUM_SET.threshold == c.qset.threshold
        assert len(cfg.QUORUM_SET.inner_sets) == 3
        for got, want in zip(cfg.QUORUM_SET.inner_sets,
                             c.qset.inner_sets):
            assert got.threshold == want.threshold
            assert got.validators == want.validators
        # KNOWN_PEERS point at topology neighbors' overlay ports
        ports = {n.peer_port for n in c.nodes}
        for addr in cfg.KNOWN_PEERS:
            assert int(addr.rsplit(":", 1)[1]) in ports


def test_tiered_links_match_topology_degrees():
    """tiered_links is the SAME edge list the in-process builder wires:
    intra-org complete graphs + braided inter-org ring (+ watcher
    uplinks), no self-links, no duplicates."""
    org_ids = [[bytes([o, i]) for i in range(3)] for o in range(3)]
    links = topologies.tiered_links(org_ids)
    assert len(links) == 9 + 9                     # 3×C(3,2) + 9 cross
    assert all(a != b for a, b, _ in links)
    assert len({frozenset((a, b)) for a, b, _ in links}) == len(links)
    watchers = [bytes([9, w]) for w in range(2)]
    wlinks = topologies.tiered_links(org_ids, watchers)
    assert len(wlinks) == len(links) + 2 * len(watchers)
    # a 1-org column must not self-link on the wrap-around ring
    solo = topologies.tiered_links([[b"a"], [b"b"], [b"c"]])
    assert all(a != b for a, b, _ in solo)
    # a 2-org braid emits each wrap-around cross pair from both sides;
    # the undirected dedupe must keep exactly one (the harness reads
    # its expected mesh degree off this list)
    two = topologies.tiered_links([[b"a0", b"a1"], [b"b0", b"b1"]])
    assert len({frozenset((x, y)) for x, y, _ in two}) == len(two)
    assert len(two) == 2 + 2        # 1 intra per org + 2 cross pairs


def test_merge_trace_docs_wall_clock_alignment():
    """The multi-process merge: dumptrace exports from separate
    processes align on the wall-clock anchor, keep distinct lanes, and
    stitch hash-keyed flood hops into cross-lane flow chains."""
    from stellar_core_tpu.util.tracemerge import merge_trace_docs

    def doc(t0_wall, pid, label, ts_us, name):
        return {"traceEvents": [
            {"ph": "i", "name": name, "pid": pid, "tid": 1,
             "ts": ts_us, "args": {"hash": "abcd1234"}},
            {"ph": "b", "name": "tx.e2e", "cat": "tx", "pid": pid,
             "tid": 1, "ts": ts_us, "id": "abcd1234", "args": {}},
        ], "otherData": {"t0_wall": t0_wall, "pid": pid,
                         "label": label, "dropped_events": 0}}

    a = doc(100.0, 7, "node00", 50.0, "flood.send")
    b = doc(100.5, 7, "node01", 10.0, "flood.recv")   # colliding pid
    merged = merge_trace_docs([a, b])
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert len(pids) == 2                          # collision resolved
    # node01 started 0.5s later: its events shift +500000us, so the
    # recv lands AFTER the send despite a smaller local ts
    send = next(e for e in evs if e.get("name") == "flood.send")
    recv = next(e for e in evs if e.get("name") == "flood.recv")
    assert recv["ts"] == pytest.approx(500010.0)
    assert send["ts"] == pytest.approx(50.0)
    # the hash crossed two lanes -> one s→f flow chain in ts order
    flows = [e for e in evs if e.get("cat") == "flood"
             and e.get("ph") in ("s", "t", "f")]
    assert [f["ph"] for f in sorted(flows, key=lambda e: e["ts"])] \
        == ["s", "f"]
    # async ids are label-scoped so the two tx.e2e tracks stay apart
    ids = {e["id"] for e in evs if e.get("ph") == "b"}
    assert ids == {"node00:abcd1234", "node01:abcd1234"}
    # caller's documents were not mutated
    assert a["traceEvents"][0]["pid"] == 7
    # and both original docs still carry their own anchor
    assert merged["otherData"]["nodes"] == ["node00", "node01"]

    # an empty doc must not shift later lanes onto the wrong label,
    # and an unanchored doc (recorder never start()ed → t0_wall 0.0,
    # e.g. a churn-restarted process) must not poison the base anchor
    unanchored = {"traceEvents": [
        {"ph": "i", "name": "boot", "pid": 3, "tid": 1, "ts": 5.0,
         "args": {}}],
        "otherData": {"t0_wall": 0.0, "pid": 3, "label": "",
                      "dropped_events": 0}}
    m2 = merge_trace_docs([{"traceEvents": []}, a, unanchored],
                          labels=["dead", "node00", "fresh"])
    assert m2["otherData"]["nodes"] == ["node00", "fresh"]
    send2 = next(e for e in m2["traceEvents"]
                 if e.get("name") == "flood.send")
    boot = next(e for e in m2["traceEvents"]
                if e.get("name") == "boot")
    assert send2["ts"] == pytest.approx(50.0)   # base = node00's anchor
    assert boot["ts"] == pytest.approx(5.0)     # unanchored: offset 0


# ---------------------------------------------------------- process legs --
def test_single_node_port_file_sigterm_and_restart(tmp_path):
    """The `run` lifecycle satellites on one real subprocess: ephemeral
    HTTP_PORT=0 reported via --port-file and the `info` route, graceful
    SIGTERM (exit 0 through the drain path), and a restart from the
    persisted data_dir that keeps the closed chain."""
    c = Cluster(1, 1, str(tmp_path), close_time=0.3)
    with c:
        c.start_all(90.0)
        node = c.nodes[0]
        # the satellite contract: port file exists and matches info
        assert os.path.exists(node.port_file)
        info = node.get("info")["info"]
        assert info["http_port"] == node.http_port
        c.wait_slot(3, 45.0)
        lcl_before = c.lcl(node)
        rcs = c.stop_all(graceful=True)
        assert rcs[node.name] == 0, rcs
        # restart from persisted state: the chain continues, no new-db
        c.spawn(node)
        c.wait_ready(60.0, nodes=[node])
        c.wait_slot(lcl_before + 1, 45.0)
        assert c.lcl(node) >= lcl_before
        rcs = c.stop_all(graceful=True)
        assert rcs[node.name] == 0, rcs


def test_cluster_smoke_3_processes(tmp_path):
    """Tier-1 acceptance smoke: three real node processes on ephemeral
    ports converge ≥3 slots over real TCP with byte-identical headers,
    every node serves a healthy clusterstatus, a raw envelope rides
    the `tx` route end to end, and teardown is clean."""
    c = Cluster(3, 1, str(tmp_path), close_time=0.4)
    with c:
        c.start_all(120.0)
        c.wait_mesh(60.0)
        c.wait_slot(3, 60.0)

        # every node: healthy clusterstatus + identical header chains
        upto = c.min_lcl()
        statuses = c.collect_clusterstatus(20.0, headers=f"2-{upto}")
        assert all(doc is not None and doc["healthy"]
                   for doc in statuses.values()), statuses
        assert c.headers_agree(upto, statuses)

        # raw tx route: a root self-payment built harness-side, seq
        # fetched over getledgerentry — both operator routes exercised
        node0 = c.nodes[0]
        res = c.submit_tx(node0, _root_self_payment(c, node0))
        assert res["status"] in ("PENDING", "DUPLICATE"), res
        assert c.drain_pending(node0, 45.0)

        # telemetry scrape over HTTP (ISSUE 10): `run` nodes sample on
        # the wall clock by default; two incremental sweeps must not
        # re-serve old samples, and the merged summary + SLO sweep
        # cover every node
        got = c.poll_timeseries(20.0)
        assert got > 0, "no telemetry samples scraped"
        first_counts = {n.name: len(n.ts_samples) for n in c.nodes}
        assert all(v > 0 for v in first_counts.values()), first_counts
        c.poll_timeseries(10.0)
        for n in c.nodes:
            cursors = [s["cursor"] for s in n.ts_samples]
            assert cursors == sorted(cursors)
            assert len(cursors) == len(set(cursors)), \
                f"{n.name}: duplicate samples re-served"
        summary = c.series_summary()
        assert summary["nodes"] == 3 and summary["samples"] > 0
        assert summary["host_load"] is not None
        slo = c.collect_slo(15.0)
        assert set(slo["per_node"]) == {n.name for n in c.nodes}
        assert slo["overall"] in ("OK", "WARN", "BREACH")

        rcs = c.stop_all(graceful=True)
        assert all(rc == 0 for rc in rcs.values()), rcs


def _root_self_payment(cluster, node) -> str:
    """Base64 TransactionEnvelope: the network root pays itself 1
    stroop, seqnum read over the admin API (getledgerentry)."""
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.crypto.sha import sha256
    from stellar_core_tpu.tx.frame import make_frame
    from stellar_core_tpu.xdr.ledger_entries import (Asset, AssetType,
                                                     LedgerEntry,
                                                     LedgerKey)
    from stellar_core_tpu.xdr.transaction import (
        DecoratedSignature, Memo, MemoType, MuxedAccount, Operation,
        OperationType, PaymentOp, Preconditions, PreconditionType,
        Transaction, TransactionEnvelope, TransactionV1Envelope,
        _OperationBody, _TxExt)
    from stellar_core_tpu.xdr.types import EnvelopeType, PublicKey

    network_id = sha256(cluster.passphrase.encode())
    root = SecretKey.from_seed(network_id)
    key = LedgerKey.account(PublicKey.ed25519(root.public_key().raw))
    doc = node.get("getledgerentry", {
        "key": base64.b64encode(key.to_bytes()).decode()})
    assert doc["state"] == "live", doc
    entry = LedgerEntry.from_bytes(base64.b64decode(doc["entry"]))
    seq = entry.data.value.seqNum + 1

    muxed = MuxedAccount.from_ed25519(root.public_key().raw)
    tx = Transaction(
        sourceAccount=muxed, fee=100, seqNum=seq,
        cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE),
        operations=[Operation(sourceAccount=None, body=_OperationBody(
            OperationType.PAYMENT, PaymentOp(
                destination=muxed,
                asset=Asset(AssetType.ASSET_TYPE_NATIVE),
                amount=1)))],
        ext=_TxExt(0))
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    probe = make_frame(env, network_id)
    env.value.signatures = [DecoratedSignature(
        hint=root.public_key().hint(),
        signature=root.sign(probe.contents_hash()))]
    return base64.b64encode(env.to_bytes()).decode()


# ---------------------------------------------- fault-schedule builders --

def test_cluster_fault_schedule_builders(tmp_path):
    """The schedule builders emit chaos specs that (a) land on BOTH
    endpoints of each cut edge, (b) name the remote node id in the
    match, and (c) round-trip through chaos.schedule_from_json — the
    exact path the `chaos?mode=install` route takes."""
    c = Cluster(3, 1, str(tmp_path))
    minority = [c.nodes[0]]
    edges = c.cut_edges(minority)
    assert edges
    for na, nb in edges:
        assert (na is c.nodes[0]) != (nb is c.nodes[0])

    per = c.partition_schedules(minority, 10.0)
    # node0 carries one spec per cut edge, each naming the far end
    specs0 = per[c.nodes[0].name]
    assert len(specs0) == len(edges)
    assert {s["match"]["peer"] for s in specs0} == \
        {n.node_id.hex() for n in c.nodes[1:]
         if any(n in e for e in edges)}
    for name, specs in per.items():
        for s in specs:
            assert s["point"] == "overlay.link"
            assert s["kind"] == "partition"
            assert s["window_s"] == 10.0
    # and the far endpoints carry the mirror spec back at node0
    for na, nb in edges:
        far = nb if na is c.nodes[0] else na
        assert any(s["match"]["peer"] == c.nodes[0].node_id.hex()
                   for s in per[far.name])

    flap = c.flap_schedules(edges, 9.0, period_s=3.0, duty=0.4)
    for specs in flap.values():
        for s in specs:
            assert s["kind"] == "flap"
            assert s["period_s"] == 3.0 and s["duty"] == 0.4
            assert s["window_s"] == 9.0

    # shape_schedules: LinkLatency speaks bits/s, the chaos Shape
    # wants bytes/s — the builder must divide by 8
    lat = topologies.LinkLatency(seed=7, cross_org_ms=(30.0, 30.0),
                                 bandwidth_bps=8_000_000.0)
    shapes = c.shape_schedules(lat, window_s=12.0)
    assert shapes
    for specs in shapes.values():
        for s in specs:
            assert s["point"] == "overlay.send"
            assert s["kind"] == "slow_link"
            assert s["bps"] == pytest.approx(1_000_000.0)
            assert s["window_s"] == 12.0
            assert s["delay_ms"] > 0

    # merge keeps every family in ONE per-node schedule (install
    # REPLACES the engine) and the wire shape parses back into specs
    merged = Cluster.merge_schedules(per, flap, shapes)
    n0 = c.nodes[0].name
    assert len(merged[n0]) == (len(per[n0]) + len(flap.get(n0, []))
                               + len(shapes.get(n0, [])))
    for specs in merged.values():
        parsed = chaos.schedule_from_json(json.loads(json.dumps(specs)))
        assert len(parsed) == len(specs)


@pytest.mark.slow
def test_cluster_partition_minority_stalls_and_rejoins(tmp_path):
    """Real-socket partition (ISSUE 20): sever one org off a 3-org
    mesh — the majority keeps externalizing through the window, the
    minority node stalls WITHOUT crashing, and after heal it rejoins
    within a bounded window with a byte-identical header chain."""
    c = Cluster(3, 1, str(tmp_path), close_time=0.4)
    with c:
        c.start_all(120.0)
        c.wait_mesh(60.0)
        c.wait_slot(2, 60.0)
        minority, majority = [c.nodes[0]], c.nodes[1:]
        # window_s=0: the cut holds until the explicit heal below, so
        # the stall observation can't race a scheduled self-heal on a
        # slow host (the scheduled-window path is the matrix cell's)
        per = c.partition_schedules(minority, window_s=0.0)
        assert c.install_schedules(per, seed=20) > 0
        lcl0 = c.min_lcl(majority)
        # the quorum-holding side rides through the window
        c.wait_slot(lcl0 + 3, 120.0, nodes=majority)
        # the minority process is alive (stalled, not crashed)
        assert c.nodes[0].alive
        minority_lcl = c.lcl(c.nodes[0])
        # heal explicitly (clear beats waiting out the window) and let
        # the jittered redial re-knit the mesh
        c.clear_all_chaos()
        c.wait_mesh(120.0)
        # bounded rejoin: the minority catches up to the network LCL
        net = c.min_lcl(majority)
        assert net > minority_lcl          # majority really advanced
        c.wait_slot(net, 150.0, nodes=minority)
        # byte-identical chains across the healed mesh, zero crashes
        upto = c.min_lcl()
        statuses = c.collect_clusterstatus(30.0, headers=f"2-{upto}")
        assert c.headers_agree(upto, statuses, expected=3), statuses
        assert all(n.alive for n in c.nodes)
        rcs = c.stop_all(graceful=True)
        assert all(rc == 0 for rc in rcs.values()), rcs


@pytest.mark.slow
def test_cluster_9_nodes_tiered_chaos(tmp_path):
    """The full ≥9-node leg: tiered 3×3 quorum of real processes, pay
    load over the wire, seeded bad-sig flood installed over the chaos
    route, a REAL kill -9 churn with restart-from-data_dir and catchup
    over the overlay — every verdict must pass."""
    res = run_cluster_scenario(str(tmp_path), n_orgs=3,
                               validators_per_org=3, close_time=0.5,
                               target_slots=5, load_rounds=2,
                               txs_per_round=200)
    assert res["safety_ok"], res
    assert res["liveness_ok"], res
    assert res["clusterstatus_ok"], res
    assert res["chaos"]["flooder_dropped"], res["chaos"]
    assert res["churn"]["caught_up"], res["churn"]
    assert res["graceful_shutdown_ok"], res["shutdown_rcs"]
    assert res["slots_externalized"] >= 7
    assert res["tps"] > 0
    assert res["ok"], res
