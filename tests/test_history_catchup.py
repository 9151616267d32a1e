"""Work framework, process manager, history publish, and catchup tests
(reference: work/test/WorkTests, history/test/HistoryTests —
TmpDirHistoryConfigurator archives, publish + catchup round trips).
"""

import contextlib
import os

import pytest

from stellar_core_tpu.catchup import (ApplyBucketsWork,
                                      CatchupConfiguration, CatchupWork,
                                      GetHistoryArchiveStateWork)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.history import (CHECKPOINT_FREQUENCY,
                                      HistoryArchiveState,
                                      checkpoint_containing,
                                      is_checkpoint_ledger,
                                      make_tmpdir_archive)
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import (BasicWork, State, WorkSequence,
                                   run_work_to_completion)

import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment


# ------------------------------------------------------------------ work --

class _FlakyWork(BasicWork):
    """Fails n times then succeeds."""

    def __init__(self, app, fail_times: int, max_retries: int = 5):
        super().__init__(app, "flaky", max_retries)
        self.fail_times = fail_times
        self.attempts = 0

    def on_run(self) -> State:
        self.attempts += 1
        if self.attempts <= self.fail_times:
            return State.WORK_FAILURE
        return State.WORK_SUCCESS


def _mini_app():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    app = Application.create(clock, cfg)
    app.start()
    return app


def test_work_retries_until_success():
    app = _mini_app()
    try:
        w = _FlakyWork(app, fail_times=2)
        assert run_work_to_completion(app, w) == State.WORK_SUCCESS
        assert w.attempts == 3
    finally:
        app.shutdown()


def test_work_fails_after_max_retries():
    app = _mini_app()
    try:
        w = _FlakyWork(app, fail_times=10, max_retries=2)
        assert run_work_to_completion(app, w) == State.WORK_FAILURE
        assert w.attempts == 3  # initial + 2 retries
    finally:
        app.shutdown()


def test_work_sequence_order():
    app = _mini_app()
    try:
        order = []

        class _W(BasicWork):
            def __init__(self, app, tag):
                super().__init__(app, f"w{tag}", 0)
                self.tag = tag

            def on_run(self):
                order.append(self.tag)
                return State.WORK_SUCCESS

        seq = WorkSequence(app, "seq", [_W(app, i) for i in range(4)])
        assert run_work_to_completion(app, seq) == State.WORK_SUCCESS
        assert order == [0, 1, 2, 3]
    finally:
        app.shutdown()


def test_process_manager_runs_commands(tmp_path):
    app = _mini_app()
    try:
        import time as _time

        def wait_for(lst, timeout=10.0):
            deadline = _time.monotonic() + timeout
            while not lst and _time.monotonic() < deadline:
                app.clock.crank(False)
                _time.sleep(0.01)  # subprocesses run in real time

        done = []
        out = tmp_path / "touched"
        app.process_manager.run_process(
            f"touch {out}", lambda code: done.append(code))
        wait_for(done)
        assert done == [0] and out.exists()
        # failing command reports nonzero
        done2 = []
        app.process_manager.run_process(
            "false", lambda code: done2.append(code))
        wait_for(done2)
        assert done2 and done2[0] != 0
    finally:
        app.shutdown()


# ------------------------------------------------------------ checkpoints --

def test_checkpoint_math():
    assert is_checkpoint_ledger(63)
    assert is_checkpoint_ledger(127)
    assert not is_checkpoint_ledger(64)
    assert checkpoint_containing(1) == 63
    assert checkpoint_containing(63) == 63
    assert checkpoint_containing(64) == 127


# --------------------------------------------------------------- publish --

def make_publishing_app(tmp_path, n_ledgers=130):
    """Standalone node with a tmpdir archive, closing n ledgers with
    scattered payments."""
    archive_root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.HISTORY = {"test": {
        "get": f"cp {archive_root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
               f"cp {{0}} {archive_root}/{{1}}",
    }}
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    app = Application.create(clock, cfg)
    app.start()
    master = m1.master_account(app)
    dests = [m1.AppAccount(app, SecretKey.from_seed(bytes([i]) * 32))
             for i in range(1, 6)]
    for d in dests:
        m1.submit(app, master.tx([op_create_account(d.account_id,
                                                    10**12)]))
    app.manual_close()
    for d in dests:
        d.sync_seq()
    for seq in range(3, n_ledgers + 1):
        if seq % 7 == 0:
            d = dests[seq % len(dests)]
            m1.submit(app, d.tx([op_payment(master.muxed, 1000)]))
        app.manual_close()
    # a checkpoint's publish rides its ledger's tail: whoever reads the
    # archive joins first
    app.herder.join_completion()
    return app, make_tmpdir_archive("test", archive_root), archive_root


def test_publish_writes_checkpoints(tmp_path):
    app, archive, root = make_publishing_app(tmp_path)
    try:
        assert app.history_manager.published_count == 2  # cp 63, 127
        assert os.path.exists(os.path.join(
            root, ".well-known/stellar-history.json"))
        with open(os.path.join(root,
                               ".well-known/stellar-history.json")) as f:
            has = HistoryArchiveState.from_json(f.read())
        assert has.current_ledger == 127
        assert os.path.exists(os.path.join(
            root, "ledger/00/00/00/ledger-0000007f.xdr.gz"))
        assert os.path.exists(os.path.join(
            root, "transactions/00/00/00/transactions-0000007f.xdr.gz"))
        for hex_hash in has.bucket_hashes():
            assert os.path.exists(os.path.join(
                root, f"bucket/{hex_hash[:2]}/{hex_hash[2:4]}/"
                      f"{hex_hash[4:6]}/bucket-{hex_hash}.xdr.gz"))
    finally:
        app.shutdown()


# --------------------------------------------------------------- catchup --

def test_catchup_complete_replay(tmp_path):
    """Fresh node replays the whole published history and lands on the
    identical chain (north-star path, SURVEY.md §3.3)."""
    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])
        master_balance_a = m1.app_account_entry(
            app_a, m1.master_account(app_a).account_id).balance

        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        clock_b = VirtualClock(ClockMode.VIRTUAL_TIME)
        app_b = Application.create(clock_b, cfg_b)
        app_b.start()
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(app_b, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 127
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a
            bal_b = m1.app_account_entry(
                app_b, m1.master_account(app_b).account_id).balance
            assert bal_b == master_balance_a
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_minimal_bucket_apply(tmp_path):
    """Bucket-apply fast-forward assumes checkpoint state without
    replay."""
    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])

        cfg_c = get_test_config()
        cfg_c.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        clock_c = VirtualClock(ClockMode.VIRTUAL_TIME)
        app_c = Application.create(clock_c, cfg_c)
        # do NOT start (no genesis): state comes purely from buckets
        try:
            has_work = GetHistoryArchiveStateWork(app_c, archive)
            assert run_work_to_completion(app_c, has_work) == \
                State.WORK_SUCCESS
            import tempfile
            work = ApplyBucketsWork(app_c, archive, has_work.has,
                                    tempfile.mkdtemp(prefix="ab-"))
            assert run_work_to_completion(app_c, work,
                                          timeout_virtual=1000) == \
                State.WORK_SUCCESS
            assert app_c.ledger_manager.get_last_closed_ledger_num() == 127
            assert app_c.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a
            # an account created in ledger 2 exists with its balance
            dest = m1.AppAccount(app_c, SecretKey.from_seed(b"\x01" * 32))
            acc = m1.app_account_entry(app_c, dest.account_id)
            assert acc is not None
        finally:
            app_c.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_to_specific_ledger(tmp_path):
    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                   cfg_b)
        app_b.start()
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=63))
            assert run_work_to_completion(app_b, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 63
            hash_a63 = bytes(app_a.database.query_one(
                "SELECT ledgerhash FROM ledgerheaders "
                "WHERE ledgerseq=63")[0])
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a63
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_with_tpu_batch_prevalidation(tmp_path):
    """The north-star path: checkpoint signatures batch-verified on the
    device before apply; identical chain, near-zero sync fallbacks
    (SURVEY.md §3.3)."""
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier

    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        hash_a = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=127")[0])
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        cfg_b.SIGNATURE_VERIFY_BACKEND = "tpu"
        app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                   cfg_b)
        app_b.start()
        try:
            # long batch_grace: the test must deterministically observe
            # the batch results being consumed (production default is a
            # 50ms bounded stall with sync fallback)
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0),
                               batch_grace=60.0)
            assert work.batch_verifier is not None
            assert run_work_to_completion(app_b, work,
                                          timeout_virtual=3000) == \
                State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 127
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a
            # the batch actually carried the verifies
            hits = sum(cw.prevalidated.hits
                       for cw in work.applied_checkpoints
                       if cw.prevalidated is not None)
            misses = sum(cw.prevalidated.misses
                         for cw in work.applied_checkpoints
                         if cw.prevalidated is not None)
            assert hits > 0
            assert misses == 0  # single-signer txs: all cache hits
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def feed_externalized_slot(app_a, app_b, seq):
    """Hand app_b the externalized value + tx set for app_a's ledger
    `seq`, as the overlay would after SCP externalizes."""
    from stellar_core_tpu.herder.tx_set import TxSetFrame
    from stellar_core_tpu.xdr.ledger import (GeneralizedTransactionSet,
                                             LedgerHeader, TransactionSet)
    hdr_row = app_a.database.query_one(
        "SELECT data FROM ledgerheaders WHERE ledgerseq=?", (seq,))
    header = LedgerHeader.from_bytes(bytes(hdr_row[0]))
    set_row = app_a.database.query_one(
        "SELECT isgeneralized, txset FROM txsethistory "
        "WHERE ledgerseq=?", (seq,))
    xdr_set = GeneralizedTransactionSet.from_bytes(
        bytes(set_row[1])) if set_row[0] else \
        TransactionSet.from_bytes(bytes(set_row[1]))
    frame = TxSetFrame(xdr_set, app_b.config.network_id())
    app_b.herder.pending_envelopes.add_tx_set(
        frame.get_contents_hash(), frame)
    app_b.herder.value_externalized_from_scp(
        seq, header.scpValue.to_bytes())


def crank_until_lcl(app, target, seconds=60):
    """Crank `app` until its LCL reaches `target` (or `seconds` of real
    time pass: the archive's `cp` commands run in real time)."""
    import time as _time
    deadline = _time.monotonic() + seconds
    while app.ledger_manager.get_last_closed_ledger_num() < target \
            and _time.monotonic() < deadline:
        if app.clock.crank(False) == 0:
            _time.sleep(0.002)


def test_out_of_sync_node_recovers_via_catchup(tmp_path):
    """A node far behind the network buffers an externalized value with
    a ledger gap, the CatchupManager fills the gap from the archive, and
    the buffered ledgers then apply (reference: CatchupManagerImpl +
    herder tracking states, SURVEY.md §5.3)."""
    app_a, archive, root = make_publishing_app(tmp_path, n_ledgers=130)
    try:
        # node A closes one more ledger beyond the checkpoint
        app_a.manual_close()  # 131
        assert app_a.ledger_manager.get_last_closed_ledger_num() == 131

        # node B: fresh, same network, archive configured for reads
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        # get-only: a catching-up node must not overwrite the
        # archive another node writes (one writer per archive)
        cfg_b.HISTORY = {n: {"get": c["get"]}
                         for n, c in app_a.config.HISTORY.items()}
        app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                   cfg_b)
        app_b.start()
        try:
            # hand B the externalized values for slots 128..131 with a
            # gap (B is at ledger 1): values rebuilt from A's chain
            for seq in (128, 129, 130, 131):
                feed_externalized_slot(app_a, app_b, seq)

            # gap detected → catchup runs → buffered values drain
            assert app_b.catchup_manager.catchups_started == 1
            crank_until_lcl(app_b, 131)
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 131
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                app_a.ledger_manager.get_last_closed_ledger_hash()
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_to_midcheckpoint_target_then_second_gap(tmp_path):
    """Catchup must stop exactly at the requested target ledger even
    mid-checkpoint (no overshoot past buffered slots), and a later gap
    must trigger a second catchup (regression: a stale buffered entry
    used to wedge gap detection forever)."""
    app_a, archive, root = make_publishing_app(tmp_path, n_ledgers=130)
    try:
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        # get-only: a catching-up node must not overwrite the
        # archive another node writes (one writer per archive)
        cfg_b.HISTORY = {n: {"get": c["get"]}
                         for n, c in app_a.config.HISTORY.items()}
        app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                   cfg_b)
        app_b.start()

        def feed_slot(seq):
            feed_externalized_slot(app_a, app_b, seq)

        try:
            # slot 100 is mid-checkpoint (checkpoints end at 63, 127)
            feed_slot(100)
            assert app_b.catchup_manager.catchups_started == 1
            crank_until_lcl(app_b, 100)
            # catchup replayed exactly to 99, then the buffered slot
            # 100 applied — NOT the whole checkpoint through 127
            assert app_b.ledger_manager.get_last_closed_ledger_num() \
                == 100
            assert not app_b.herder._buffered_values

            # a later gap must still be detected and recovered
            feed_slot(125)
            assert app_b.catchup_manager.catchups_started == 2
            crank_until_lcl(app_b, 125)
            assert app_b.ledger_manager.get_last_closed_ledger_num() \
                == 125
            row = app_a.database.query_one(
                "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
                (125,))
            assert app_b.ledger_manager.get_last_closed_ledger_hash() \
                == bytes(row[0])
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


@contextlib.contextmanager
def _online_catchup_on_the_device(app_a, slot):
    """A node with the device backend, at genesis, handed slot `slot` of
    app_a's chain: yields it once the CatchupManager's catchup and the
    buffered slot have brought it there, on app_a's hash."""
    cfg_b = get_test_config()
    cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
    cfg_b.SIGNATURE_VERIFY_BACKEND = "tpu"
    cfg_b.HISTORY = {n: {"get": c["get"]}
                     for n, c in app_a.config.HISTORY.items()}
    app_b = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                               cfg_b)
    app_b.start()
    try:
        feed_externalized_slot(app_a, app_b, slot)
        assert app_b.catchup_manager.catchups_started == 1
        # long batch_grace, as in the offline twin above: the first
        # probe waits for the batch, so what the table answers does not
        # depend on how fast this machine's device is
        app_b.catchup_manager._running._sequence[0].batch_grace = 60.0
        crank_until_lcl(app_b, slot, seconds=120)
        assert app_b.ledger_manager.get_last_closed_ledger_num() == slot
        assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
            bytes(app_a.database.query_one(
                "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?",
                (slot,))[0])
        yield app_b
    finally:
        app_b.shutdown()


def test_online_catchup_answers_from_the_device_table(tmp_path):
    """A live node that falls behind runs the catchup the `catchup`
    command runs: its checkpoint's signatures go to the device resolved
    against state, and every check of the replay is one the resolver
    foresaw (`crypto.prevalidated.*`, published when a checkpoint's
    work ends)."""
    app_a, archive, root = make_publishing_app(tmp_path, n_ledgers=70)
    try:
        with _online_catchup_on_the_device(app_a, 64) as app_b:
            counters = app_b.metrics.to_json()
            assert counters["crypto.prevalidated.hit"]["count"] > 0
            assert counters["crypto.prevalidated.miss.unknown"][
                "count"] == 0
    finally:
        app_a.shutdown()


def test_online_catchup_prefetches_the_second_checkpoint(tmp_path):
    """Over a gap of two checkpoints the second one's batch is
    dispatched while the first applies: `catchup.batch.lead` is how long
    its verdicts were back before its first ledger asked."""
    app_a, archive, root = make_publishing_app(tmp_path, n_ledgers=130)
    try:
        with _online_catchup_on_the_device(app_a, 128) as app_b:
            assert app_b.metrics.to_json()[
                "catchup.batch.lead"]["count"] == 1
    finally:
        app_a.shutdown()


# ------------------------------------------------- tx-results verification --

def _rewrite_results_file(root, checkpoint, mutate):
    """Load, mutate, and re-gzip one archived results file."""
    import gzip
    import io as _io
    from stellar_core_tpu.history.archive import file_path
    from stellar_core_tpu.util.xdr_stream import read_record, write_record
    from stellar_core_tpu.xdr.ledger import TransactionHistoryResultEntry
    path = os.path.join(root, file_path("results", checkpoint))
    entries = []
    with gzip.open(path, "rb") as f:
        bio = _io.BytesIO(f.read())
    while True:
        rec = read_record(bio)
        if rec is None:
            break
        entries.append(TransactionHistoryResultEntry.from_bytes(rec))
    mutate(entries)
    out = _io.BytesIO()
    for e in entries:
        write_record(out, e.to_bytes())
    with gzip.open(path, "wb") as f:
        f.write(out.getvalue())


def test_catchup_rejects_results_diverging_from_headers(tmp_path, caplog):
    """Archived results that do not hash to the signed header chain fail
    catchup at download-verify time, naming the ledger (reference:
    historywork/VerifyTxResultsWork.cpp)."""
    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        def corrupt(entries):
            assert entries, "expected archived results"
            res = entries[0].txResultSet.results[0].result
            res.feeCharged += 1          # silent history tamper
        _rewrite_results_file(root, 127, corrupt)

        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        app_b = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg_b)
        app_b.start()
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            with caplog.at_level("ERROR"):
                final = run_work_to_completion(app_b, work,
                                               timeout_virtual=3000)
            assert final == State.WORK_FAILURE
            assert any("do not match the signed header chain" in r.message
                       for r in caplog.records)
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_replay_divergence_fails_at_offending_ledger(tmp_path, caplog):
    """If the (header-consistent) archive disagrees with what replay
    produces, catchup fails AT the offending ledger and names the tx
    (reference: DownloadVerifyTxResultsWork anchoring the replay).
    Simulated by injecting a verified-but-wrong results anchor."""
    from stellar_core_tpu.catchup.catchup_work import (
        DownloadVerifyTxResultsWork)

    app_a, archive, root = make_publishing_app(tmp_path)
    try:
        cfg_b = get_test_config()
        cfg_b.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
        app_b = Application.create(
            VirtualClock(ClockMode.VIRTUAL_TIME), cfg_b)
        app_b.start()
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))

            # let catchup build its checkpoint works, then replace the
            # first checkpoint's anchor with a doctored one
            from stellar_core_tpu.work import run_work_to_completion
            clock = app_b.clock

            def crank_until(pred, limit=20000):
                import time as _time
                work.start_work(None)
                for _ in range(limit):
                    work.crank_work()
                    if pred() or work.is_done():
                        return
                    if clock.crank(False) == 0:
                        clock.crank(True)
                        _time.sleep(0.002)  # archive cp runs in real time

            crank_until(lambda: work.applied_checkpoints)
            assert work.applied_checkpoints
            acw = work.applied_checkpoints[0]
            rw = acw.results_work
            # run the real anchor to completion, then poison one entry
            import time as _time
            while not rw.is_done():
                rw.ensure_started(acw.wake_up)
                rw.crank_work()
                if clock.crank(False) == 0:
                    clock.crank(True)
                    _time.sleep(0.002)
            assert rw.get_state() == State.WORK_SUCCESS
            poisoned_seq = sorted(rw.results_by_seq)[0]
            # simulate a replay that diverges from (self-consistent)
            # verified history: doctor the expected results AND the
            # verified header's result hash together, as a divergent
            # network's archive would carry them
            from stellar_core_tpu.crypto.sha import sha256
            entry = rw.results_by_seq[poisoned_seq]
            entry.txResultSet.results[0].result.feeCharged += 1
            acw.headers[poisoned_seq].header.txSetResultHash = \
                sha256(entry.txResultSet.to_bytes())

            import time as _time
            with caplog.at_level("ERROR"):
                for _ in range(40000):
                    if work.is_done():
                        break
                    work.crank_work()
                    if clock.crank(False) == 0:
                        clock.crank(True)
                        _time.sleep(0.002)
            assert work.get_state() == State.WORK_FAILURE
            msgs = [r.message for r in caplog.records]
            assert any(f"replay diverged at ledger {poisoned_seq}" in m
                       for m in msgs), msgs
            # replay stopped AT the offending ledger, not at the end
            assert app_b.ledger_manager.get_last_closed_ledger_num() \
                == poisoned_seq
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


# ------------------------------- recent-qsets + single-header audits --

def test_check_single_ledger_header_work(tmp_path):
    """Archive audit (reference: CheckSingleLedgerHeaderWork.cpp): an
    archived header matching the trusted hash passes; a divergent hash
    fails loudly."""
    from stellar_core_tpu.catchup.catchup_work import (
        CheckSingleLedgerHeaderWork)
    app, archive, root = make_publishing_app(tmp_path)
    try:
        row = app.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=100")
        good = CheckSingleLedgerHeaderWork(
            app, archive, 100, bytes(row[0]), str(tmp_path / "dl1"))
        assert run_work_to_completion(app, good) == State.WORK_SUCCESS
        bad = CheckSingleLedgerHeaderWork(
            app, archive, 100, b"\x13" * 32, str(tmp_path / "dl2"))
        assert run_work_to_completion(app, bad) == State.WORK_FAILURE
    finally:
        app.shutdown()


def test_fetch_recent_qsets_work(tmp_path):
    """SCP-state recovery from archives (reference:
    FetchRecentQsetsWork.cpp): a fresh node learns the validators'
    quorum sets from the published SCP files."""
    from stellar_core_tpu.catchup.catchup_work import FetchRecentQsetsWork
    from stellar_core_tpu.scp import local_node as ln
    from stellar_core_tpu.simulation import topologies

    archive_root = str(tmp_path / "archive")

    def cfg_gen(cfg):
        if cfg.PEER_PORT == 35000:     # only node 0 publishes
            cfg.HISTORY = {"sim": {
                "get": f"cp {archive_root}/{{0}} {{1}}",
                "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
                       f"cp {{0}} {archive_root}/{{1}}",
            }}

    sim = topologies.core(3, configure=cfg_gen)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(
            lambda: sim.have_all_externalized(66),
            timeout_virtual_seconds=600), "quorum stalled"
        # let the publish subprocess finish (real time)
        import time as _time
        deadline = _time.monotonic() + 20
        app0 = sim.apps()[0]
        while app0.history_manager.published_count < 1 and \
                _time.monotonic() < deadline:
            sim.clock.crank(False)
            _time.sleep(0.02)
        assert app0.history_manager.published_count >= 1
    finally:
        sim.stop_all_nodes()

    from stellar_core_tpu.history import make_tmpdir_archive
    archive = make_tmpdir_archive("sim", archive_root)
    app = _mini_app()
    try:
        work = FetchRecentQsetsWork(app, archive, str(tmp_path / "dl"))
        assert run_work_to_completion(app, work) == State.WORK_SUCCESS
        # all three validators inferred, pinning the shared qset
        assert len(work.inferred) == 3
        qhashes = set(work.inferred.values())
        assert len(qhashes) == 1
        qh = qhashes.pop()
        assert qh in work.qsets
        # and persisted for the local herder to consult
        row = app.database.query_one(
            "SELECT qset FROM scpquorums WHERE qsethash=?", (qh,))
        assert row is not None
        assert ln.qset_hash(work.qsets[qh]) == qh
    finally:
        app.shutdown()
