"""Streaming parallel catchup (catchup/pipeline.py): the pipelined
replay path is pinned byte-identical to the sequential reference, the
coalescer's padding math is exact, device prevalidation carries the
verifies, injected archive faults drain-and-resume deterministically, a
crash mid-apply resumes from the last committed ledger, and the
`trace_report --catchup` occupancy report proves stage overlap from a
real trace.
"""

import json
import os
import sys
import time

import pytest

from stellar_core_tpu.catchup import (CatchupConfiguration, CatchupWork,
                                      StreamingCatchupWork)
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.ops.verifier import prevalidate_coalesce
from stellar_core_tpu.util import chaos
from stellar_core_tpu.util.chaos import (ChaosEngine, FaultSpec,
                                         SimulatedCrash)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State, run_work_to_completion

import test_history_catchup as hc

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leftover_engine():
    """Every test starts and ends with chaos disabled."""
    chaos.uninstall()
    yield
    chaos.uninstall()


def _fresh_node(app_a, **cfg_overrides):
    cfg = get_test_config()
    cfg.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _header_chain(app):
    return [(int(r[0]), bytes(r[1]), bytes(r[2]))
            for r in app.database.query_all(
                "SELECT ledgerseq, ledgerhash, data FROM ledgerheaders "
                "ORDER BY ledgerseq")]


# ----------------------------------------------------------- coalescing --

def test_prevalidate_coalesce_padding_math():
    # empty window: nothing to dispatch
    assert prevalidate_coalesce([], 4) == 0
    # 300+300: bucket(600)=1024 == bucket(300)+bucket(300): fusing
    # halves the dispatches at zero padding cost
    assert prevalidate_coalesce([300, 300], 4) == 2
    # 512+10: bucket(522)=1024 > 512+16: fusing pads the big bucket to
    # carry the tiny one — keep them separate
    assert prevalidate_coalesce([512, 10], 4) == 1
    # empty checkpoints fuse for free and don't break a fusion chain
    assert prevalidate_coalesce([300, 0, 300], 4) == 3
    assert prevalidate_coalesce([0, 0, 5], 4) == 3
    # the window cap bounds the fusion regardless of the math
    assert prevalidate_coalesce([5, 0, 0, 0, 0, 0], 3) == 3


# ---------------------------------------------------------- differential --

def test_pipeline_differential_vs_sequential(tmp_path):
    """The pinning test: pipelined catchup lands on a final state
    byte-identical to sequential catchup — same LCL, same hash, same
    full ledgerheaders chain (seq, hash, and header XDR per row)."""
    # three checkpoints (63, 127, 191): enough depth for the byte
    # budget to actually park admission behind a slow apply head
    app_a, archive, root = hc.make_publishing_app(tmp_path,
                                                  n_ledgers=200)
    try:
        app_seq = _fresh_node(app_a)
        try:
            work = CatchupWork(app_seq, archive,
                               CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(app_seq, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            chain_seq = _header_chain(app_seq)
        finally:
            app_seq.shutdown()

        # small window + tight byte budget: the admission gate and
        # byte-budget backpressure both exercise without changing the
        # replayed bytes
        app_pipe = _fresh_node(
            app_a, CATCHUP_PIPELINE_AHEAD_CHECKPOINTS=2,
            CATCHUP_PIPELINE_BYTE_BUDGET=1)
        try:
            work = StreamingCatchupWork(app_pipe, archive,
                                        CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(app_pipe, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_pipe.ledger_manager \
                .get_last_closed_ledger_num() == 191
            chain_pipe = _header_chain(app_pipe)
            report = work.stats.report()
        finally:
            app_pipe.shutdown()

        assert chain_pipe == chain_seq
        # stats carry the artifact's stage shape and saw every item
        assert set(report["stages"]) == {"download", "verify",
                                         "prevalidate", "apply"}
        assert report["stages"]["download"]["items"] == 3  # cp 63..191
        assert report["stages"]["verify"]["items"] == 3
        assert report["stages"]["apply"]["items"] == 190  # ledgers 2..191
        assert report["queues"]["bytes_hwm"] > 0
        # byte budget of 1 forces at least one admission stall episode
        assert report["queues"]["backpressure_stalls"] >= 1
    finally:
        app_a.shutdown()


# ------------------------------------------------- device prevalidation --

def test_pipeline_tpu_batch_prevalidation(tmp_path):
    """Coalesced device batches carry the replay's signature verifies:
    every checkpoint signature lands as a prevalidation hit, none fall
    through to the native path."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        app_b = _fresh_node(app_a, SIGNATURE_VERIFY_BACKEND="tpu")
        try:
            # long batch_grace: deterministically observe the batch
            # results being consumed (production default is a 50ms
            # bounded stall with sync fallback)
            work = StreamingCatchupWork(app_b, archive,
                                        CatchupConfiguration(to_ledger=0),
                                        batch_grace=60.0)
            assert work.batch_verifier is not None
            assert run_work_to_completion(app_b, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_b.ledger_manager \
                .get_last_closed_ledger_num() == 127
            assert work.batches, "no coalesced batch was dispatched"
            hits = sum(b.pv.hits for b in work.batches
                       if b.pv is not None)
            misses = sum(b.pv.misses for b in work.batches
                         if b.pv is not None)
            assert hits > 0
            assert misses == 0  # single-signer txs: all table hits
            assert not any(b.failed for b in work.batches)
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


# ----------------------------------------------------------------- chaos --

@pytest.mark.chaos
def test_pipeline_archive_io_error_drains_and_resumes(tmp_path):
    """Injected archive fetch faults mid-stream: the hit stage retries
    (GetRemoteFileWork's seeded backoff), the pipeline drains and
    resumes without wedging, the final chain is intact — and the whole
    fault schedule replays identically from the same seed."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])

        def one_run():
            eng = ChaosEngine(11, [FaultSpec(
                "history.get", "io_error", start=2, count=2)])
            chaos.install(eng)
            app_b = _fresh_node(app_a)
            try:
                work = StreamingCatchupWork(
                    app_b, archive, CatchupConfiguration(to_ledger=0))
                assert run_work_to_completion(app_b, work,
                                              timeout_virtual=3000) == \
                    State.WORK_SUCCESS
                assert app_b.ledger_manager \
                    .get_last_closed_ledger_num() == 127
                assert app_b.ledger_manager \
                    .get_last_closed_ledger_hash() == hash_a
            finally:
                chaos.uninstall()
                app_b.shutdown()
            return list(eng.log), dict(eng.injected)

        log1, injected1 = one_run()
        log2, injected2 = one_run()
        assert injected1["chaos.injected.io_error"] == 2
        # same seed, same schedule: the fault replay is deterministic
        assert log1 == log2
        assert injected1 == injected2
    finally:
        app_a.shutdown()


@pytest.mark.chaos
def test_pipeline_crash_mid_apply_resumes_from_committed(tmp_path):
    """`crash` at the catchup.apply seam mid-replay: the node dies
    between committed ledgers; a restart from the same DB + bucket dir
    resumes from the last committed ledger and a fresh streaming catchup
    completes to the identical chain."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])
        cfg = get_test_config()
        cfg.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        cfg.DATABASE = f"sqlite3://{tmp_path}/node_b.db"
        cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets_b")
        app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                   cfg)
        app_b.start()
        # fresh node replays 2..127; apply hit i is ledger 2+i, so
        # start=40 crashes entering ledger 42 with 41 committed
        chaos.install(ChaosEngine(8, [FaultSpec(
            "catchup.apply", "crash", start=40, count=1)]))
        crashed = False
        try:
            work = StreamingCatchupWork(app_b, archive,
                                        CatchupConfiguration(to_ledger=0))
            try:
                run_work_to_completion(app_b, work, timeout_virtual=3000)
            except SimulatedCrash:
                crashed = True
        finally:
            chaos.uninstall()
        assert crashed
        # abandon the crashed process image (no shutdown — a crash
        # doesn't get to run destructors); restart from the same files.
        # A real crash takes the image's threads with it; here they
        # live on, and ledger 41's history tail, committing from the
        # dead image's completion worker while the restarted node's
        # `Database.initialize()` holds a read snapshot, is "database
        # is locked" at once (a stale WAL snapshot, which no busy
        # timeout waits out): seen under `-n 6`. So do to the image what
        # a kill does: drop its queued tails, let the one in flight end.
        app_b.ledger_manager.discard_pending_completion()
        app_b.ledger_manager.join_completion(reraise=False)
        app_b2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                    cfg)
        app_b2.start()
        try:
            assert app_b2.ledger_manager \
                .get_last_closed_ledger_num() == 41
            work = StreamingCatchupWork(app_b2, archive,
                                        CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(app_b2, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_b2.ledger_manager \
                .get_last_closed_ledger_num() == 127
            assert app_b2.ledger_manager \
                .get_last_closed_ledger_hash() == hash_a
        finally:
            app_b2.shutdown()
    finally:
        app_a.shutdown()


# ---------------------------------------------------------- trace report --

def test_trace_report_catchup_occupancy(tmp_path):
    """`trace_report --catchup` over a real traced pipeline run: the
    stage table carries busy time for every stage, the device batches
    appear as dispatch/land intervals, and queue high-water marks come
    from the queue instants."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        app_b = _fresh_node(app_a, SIGNATURE_VERIFY_BACKEND="tpu")
        app_b.flight_recorder.start()
        try:
            work = StreamingCatchupWork(app_b, archive,
                                        CatchupConfiguration(to_ledger=0),
                                        batch_grace=60.0)
            assert run_work_to_completion(app_b, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            doc = app_b.flight_recorder.to_chrome_trace()
        finally:
            app_b.flight_recorder.stop()
            app_b.shutdown()
        path = str(tmp_path / "catchup_trace.json")
        with open(path, "w") as f:
            json.dump(doc, f)

        summary = trace_report.report_catchup(path)
        assert set(summary["stages"]) == {"download", "verify",
                                          "device", "apply"}
        assert summary["wall_ms"] > 0
        for stage in ("download", "verify", "apply"):
            assert summary["stages"][stage]["busy_ms"] > 0
            assert summary["stages"][stage]["items"] > 0
        # the device batches landed as paired dispatch/land instants
        assert summary["stages"]["device"]["items"] >= 1
        assert summary["queues"]["bytes_hwm"] > 0
        assert summary["queues"]["ready_hwm"] >= 1
        assert "device_idle" in summary
        assert summary["overlap"]["device_busy_while_download_ms"] >= 0
    finally:
        app_a.shutdown()
