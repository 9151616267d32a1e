"""Catchup under injected faults (docs/CHAOS.md, docs/CATCHUP.md):
archive fetch faults retry through the work scheduler's seeded backoff
and replay identically from one seed, and a crash between replayed
ledgers resumes from the last committed one.
"""

import pytest

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util import chaos
from stellar_core_tpu.util.chaos import (ChaosEngine, FaultSpec,
                                         SimulatedCrash)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import State, run_work_to_completion

import test_history_catchup as hc


@pytest.fixture(autouse=True)
def _no_leftover_engine():
    """Every test starts and ends with chaos disabled."""
    chaos.uninstall()
    yield
    chaos.uninstall()


def _node(app_a, **fields):
    cfg = get_test_config()
    cfg.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
    for k, v in fields.items():
        setattr(cfg, k, v)
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _catch_up(app, archive):
    work = CatchupWork(app, archive, CatchupConfiguration(to_ledger=0))
    return run_work_to_completion(app, work, timeout_virtual=3000)


def _hash_at(app, seq):
    return bytes(app.database.query_one(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
        (seq,))[0])


@pytest.mark.chaos
def test_archive_io_error_retries_and_replays_from_the_seed(tmp_path):
    """Two `io_error` faults at `history.get` mid-catchup: the fetch
    that was hit retries (GetRemoteFileWork's seeded backoff), the
    catchup ends on the publisher's chain, and the whole fault schedule
    replays identically from the same seed."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        hash_a = _hash_at(app_a, 127)

        def one_run():
            eng = ChaosEngine(11, [FaultSpec(
                "history.get", "io_error", start=2, count=2)])
            chaos.install(eng)
            app_b = _node(app_a)
            try:
                assert _catch_up(app_b, archive) == State.WORK_SUCCESS
                lm = app_b.ledger_manager
                assert lm.get_last_closed_ledger_num() == 127
                assert lm.get_last_closed_ledger_hash() == hash_a
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
def test_crash_mid_apply_resumes_from_the_last_committed_ledger(tmp_path):
    """`crash` at the `catchup.apply` seam mid-replay: the node dies
    between committed ledgers; a restart from the same DB + bucket dir
    reads the last committed ledger and a fresh catchup ends on the
    publisher's chain."""
    app_a, archive, root = hc.make_publishing_app(tmp_path)
    try:
        hash_a = _hash_at(app_a, 127)
        app_b = _node(
            app_a, DATABASE=f"sqlite3://{tmp_path}/node_b.db",
            BUCKET_DIR_PATH=str(tmp_path / "buckets_b"))
        # fresh node replays 2..127; apply hit i is ledger 2+i, so
        # start=40 crashes entering ledger 42 with 41 committed
        chaos.install(ChaosEngine(8, [FaultSpec(
            "catchup.apply", "crash", start=40, count=1)]))
        try:
            with pytest.raises(SimulatedCrash):
                _catch_up(app_b, archive)
        finally:
            chaos.uninstall()
        # abandon the crashed process image (no shutdown — a crash
        # doesn't get to run destructors); restart from the same files.
        # A real crash takes the image's threads with it; here they
        # live on, and ledger 41's history tail may still be committing
        # from the dead image's completion worker when the restarted
        # node opens the file (`Database.initialize()` takes the write
        # lock at its BEGIN and waits such a writer out). So do to the
        # image what a kill does: drop its queued tails, let the one in
        # flight end.
        app_b.ledger_manager.discard_pending_completion()
        app_b.ledger_manager.join_completion(reraise=False)
        app_b2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                    app_b.config)
        app_b2.start()
        try:
            lm = app_b2.ledger_manager
            assert lm.get_last_closed_ledger_num() == 41
            assert _catch_up(app_b2, archive) == State.WORK_SUCCESS
            assert lm.get_last_closed_ledger_num() == 127
            assert lm.get_last_closed_ledger_hash() == hash_a
        finally:
            app_b2.shutdown()
    finally:
        app_a.shutdown()
