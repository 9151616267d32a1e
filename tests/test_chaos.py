"""Deterministic chaos injection (ISSUE 2 tentpole).

Engine semantics (seeded schedules, hit ordinals, fault kinds), every
instrumented seam (overlay send/recv, archive get/put, DB commit,
completion queue, device verifier), the overlay send-error hardening,
the frozen-result-pair guard, the crash-point matrix over the close
phase boundaries (recovery must be byte-identical via the
`lastclosecompleted` path), the durable publish queue across a crash,
and the seeded multinode convergence scenario.
"""

import json
import os
import time

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.db.database import Database
from stellar_core_tpu.herder import make_tx_set_from_transactions
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util import chaos
from stellar_core_tpu.util.chaos import (CLOSE_CRASH_POINTS, ChaosEngine,
                                         ChaosError, FaultSpec,
                                         SimulatedCrash)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger import StellarValue
from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType
from stellar_core_tpu.xdr.transaction import (DecoratedSignature, Memo,
                                              MemoType, MuxedAccount,
                                              Operation, OperationType,
                                              PaymentOp, Preconditions,
                                              PreconditionType, Transaction,
                                              TransactionEnvelope,
                                              TransactionV1Envelope,
                                              _OperationBody, _TxExt)
from stellar_core_tpu.xdr.types import EnvelopeType

import test_ledger_close as lc
import test_overlay as ovl
from txtest_utils import op_create_account

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _no_leftover_engine():
    """Every test starts and ends with chaos disabled."""
    chaos.uninstall()
    yield
    chaos.uninstall()


# ------------------------------------------------------------ the engine --

def test_disabled_is_passthrough():
    assert chaos.ENABLED is False
    assert chaos.point("anything", b"payload", node="x") == b"payload"


def test_hit_window_scheduling_and_status():
    eng = ChaosEngine(3, [FaultSpec("p", "drop", start=1, count=2)])
    chaos.install(eng)
    assert chaos.ENABLED
    outs = [chaos.point("p", b"m") for _ in range(4)]
    assert outs[0] == b"m" and outs[3] == b"m"
    assert outs[1] is chaos.DROP and outs[2] is chaos.DROP
    st = chaos.status()
    assert st["injected"] == {"chaos.injected.drop": 2}
    assert st["points"] == {"p": 4}


def test_match_filters_by_context():
    eng = ChaosEngine(1, [FaultSpec("p", "drop", start=0, count=10,
                                    match={"node": "aa"})])
    chaos.install(eng)
    assert chaos.point("p", b"m", node="bb") == b"m"
    assert chaos.point("p", b"m", node="aa") is chaos.DROP
    # matched-hit ordinals count only matching calls
    assert eng._spec_hits[0] == 1


def test_fault_kinds():
    eng = ChaosEngine(9, [
        FaultSpec("io", "io_error"),
        FaultSpec("cr", "crash"),
        FaultSpec("co", "corrupt"),
        FaultSpec("fa", "fail"),
        FaultSpec("ha", "hang"),
    ])
    chaos.install(eng)
    with pytest.raises(ChaosError):
        chaos.point("io")
    with pytest.raises(SimulatedCrash) as exc:
        chaos.point("cr", node="deadbeef")
    assert exc.value.ctx["node"] == "deadbeef"
    out = chaos.point("co", b"\x00" * 8)
    assert out != b"\x00" * 8 and len(out) == 8
    assert sum(b != 0 for b in out) == 1   # exactly one byte flipped
    assert chaos.point("fa") is chaos.FAIL
    # hang (ISSUE 5): caller-interpreted sentinel — the backend
    # supervisor substitutes a never-completing handle for it
    assert chaos.point("ha") is chaos.HANG
    assert eng.injected["chaos.injected.hang"] == 1


def test_same_seed_reproduces_same_log():
    def run(seed):
        eng = ChaosEngine(seed, [
            FaultSpec("a", "drop", prob=0.5),
            FaultSpec("b", "drop", start=2, count=3),
        ])
        chaos.install(eng)
        for i in range(20):
            chaos.point("a", b"x")
            chaos.point("b", b"x")
        chaos.uninstall()
        return list(eng.log), dict(eng.injected)

    assert run(42) == run(42)
    assert run(42) != run(43)


def test_schedule_json_roundtrip():
    specs = [FaultSpec("p", "delay", start=1, count=2, delay_ms=5.0),
             FaultSpec("q", "drop", prob=0.25, match={"node": "aa"})]
    docs = [s.to_json() for s in specs]
    back = chaos.schedule_from_json(json.loads(json.dumps(docs)))
    assert [s.to_json() for s in back] == docs
    with pytest.raises(ValueError):
        FaultSpec("p", "not-a-kind")


def test_admin_chaos_route():
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        h = app.command_handler
        assert h.handle("chaos")["chaos"] == {"enabled": False}
        out = h.handle("chaos", {
            "mode": "install", "seed": "5",
            "schedule": json.dumps([{"point": "p", "kind": "drop"}])})
        assert out["chaos"]["enabled"] and out["chaos"]["seed"] == 5
        assert chaos.point("p", b"x") is chaos.DROP
        # injected counters surface on the metrics route too
        assert "chaos" in h.handle("metrics")
        assert h.handle("chaos", {"mode": "clear"})["status"] == "ok"
        assert chaos.ENABLED is False
        # production gate: without ALLOW_CHAOS_INJECTION the route
        # serves status but refuses install/clear
        app.config.ALLOW_CHAOS_INJECTION = False
        out = h.handle("chaos", {
            "mode": "install", "seed": "5",
            "schedule": json.dumps([{"point": "p", "kind": "drop"}])})
        assert "exception" in out
        assert chaos.ENABLED is False
        assert h.handle("chaos")["chaos"] == {"enabled": False}
    finally:
        app.shutdown()


# -------------------------------------------- overlay seams + hardening --

def test_overlay_send_io_error_takes_drop_path_not_scheduler():
    """Satellite: a transport error mid-write must tear the peer down
    through the standard drop path (floodgate unsubscribed, advert
    queue gone) and never unwind into the caller."""
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        om = apps[0].overlay_manager
        assert conn.initiator in om.get_authenticated_peers()
        node0 = apps[0].config.node_id().hex()
        chaos.install(ChaosEngine(1, [FaultSpec(
            "overlay.send", "io_error", start=0, count=1,
            match={"node": node0})]))
        from stellar_core_tpu.xdr.overlay import (MessageType,
                                                  StellarMessage)
        msg = StellarMessage(MessageType.GET_SCP_QUORUMSET,
                             b"\x01" * 32)
        conn.initiator.send_message(msg)      # must NOT raise
        assert conn.initiator.state.name == "CLOSING"
        assert conn.initiator not in om.get_authenticated_peers()
        assert id(conn.initiator) not in om._advert_queues
        assert chaos.engine().injected["chaos.injected.io_error"] == 1
    finally:
        chaos.uninstall()
        ovl.shutdown(apps)


def test_overlay_recv_corruption_drops_peer_cleanly():
    """Transport corruption lands as a MAC failure and takes the
    standard ERR_AUTH drop path on the receiving side."""
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        node1 = apps[1].config.node_id().hex()
        chaos.install(ChaosEngine(2, [FaultSpec(
            "overlay.recv", "corrupt", start=0, count=1,
            match={"node": node1})]))
        from stellar_core_tpu.xdr.overlay import (MessageType,
                                                  StellarMessage)
        conn.initiator.send_message(StellarMessage(
            MessageType.GET_SCP_QUORUMSET, b"\x02" * 32))
        conn.crank()                          # must NOT raise
        assert conn.acceptor.state.name == "CLOSING"
        assert conn.acceptor not in \
            apps[1].overlay_manager.get_authenticated_peers()
    finally:
        chaos.uninstall()
        ovl.shutdown(apps)


def test_overlay_message_drop_keeps_link_alive():
    """Pre-MAC message loss does NOT violate HMAC sequencing: the
    message vanishes, the link stays authenticated."""
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        node0 = apps[0].config.node_id().hex()
        chaos.install(ChaosEngine(3, [FaultSpec(
            "overlay.message", "drop", start=0, count=1,
            match={"node": node0})]))
        from stellar_core_tpu.xdr.overlay import (MessageType,
                                                  StellarMessage)
        before = conn.acceptor.messages_read
        conn.initiator.send_message(StellarMessage(
            MessageType.GET_SCP_QUORUMSET, b"\x03" * 32))
        conn.crank()
        assert conn.acceptor.messages_read == before   # dropped
        chaos.uninstall()
        conn.initiator.send_message(StellarMessage(
            MessageType.GET_SCP_QUORUMSET, b"\x04" * 32))
        conn.crank()
        assert conn.acceptor.messages_read == before + 1
        assert conn.initiator.state.name == "GOT_AUTH"
        assert conn.acceptor.state.name == "GOT_AUTH"
    finally:
        chaos.uninstall()
        ovl.shutdown(apps)


def test_loopback_recv_io_error_drops_receiver_not_crank_loop():
    """An injected io_error at the loopback recv seam takes the
    receiving peer's standard drop path — the simulation crank loop
    never sees the exception (TCP-path symmetry)."""
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        node1 = apps[1].config.node_id().hex()
        chaos.install(ChaosEngine(12, [FaultSpec(
            "overlay.recv", "io_error", start=0, count=1,
            match={"node": node1})]))
        from stellar_core_tpu.xdr.overlay import (MessageType,
                                                  StellarMessage)
        conn.initiator.send_message(StellarMessage(
            MessageType.GET_SCP_QUORUMSET, b"\x06" * 32))
        conn.crank()                          # must NOT raise
        assert conn.acceptor.state.name == "CLOSING"
        assert conn.acceptor not in \
            apps[1].overlay_manager.get_authenticated_peers()
    finally:
        chaos.uninstall()
        ovl.shutdown(apps)


def test_transport_seam_ignores_meaningless_sentinels():
    """A mis-kinded schedule (fail at a transport seam) must not leak
    the sentinel object into the byte stream or the scheduler: the
    frame goes out unchanged."""
    from stellar_core_tpu.overlay import LoopbackPeerConnection
    clock, apps = ovl.make_apps(2)
    try:
        conn = LoopbackPeerConnection(apps[0], apps[1])
        conn.crank()
        chaos.install(ChaosEngine(11, [
            FaultSpec("overlay.send", "fail", start=0, count=1 << 30),
            FaultSpec("overlay.recv", "fail", start=0, count=1 << 30),
        ]))
        from stellar_core_tpu.xdr.overlay import (MessageType,
                                                  StellarMessage)
        before = conn.acceptor.messages_read
        conn.initiator.send_message(StellarMessage(
            MessageType.GET_SCP_QUORUMSET, b"\x05" * 32))
        conn.crank()                          # must NOT raise
        assert conn.acceptor.messages_read == before + 1
        assert conn.initiator.state.name == "GOT_AUTH"
        assert conn.acceptor.state.name == "GOT_AUTH"
    finally:
        chaos.uninstall()
        ovl.shutdown(apps)


# ----------------------------------------------------- archive + db + cq --

def test_archive_get_failure_is_retried(tmp_path):
    """An injected archive fetch failure takes the real command-failed
    path; GetRemoteFileWork's retry succeeds once the fault clears."""
    from stellar_core_tpu.catchup.catchup_work import GetRemoteFileWork
    from stellar_core_tpu.history.archive import make_tmpdir_archive
    from stellar_core_tpu.work import run_work_to_completion
    from stellar_core_tpu.work.basic_work import State

    root = str(tmp_path / "archive")
    archive = make_tmpdir_archive("t", root)
    with open(os.path.join(root, "blob"), "w") as f:
        f.write("payload")
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        chaos.install(ChaosEngine(4, [FaultSpec(
            "history.get", "fail", start=0, count=1)]))
        local = str(tmp_path / "out")
        work = GetRemoteFileWork(app, archive, "blob", local)
        assert run_work_to_completion(app, work) == State.WORK_SUCCESS
        assert open(local).read() == "payload"
        assert chaos.engine().injected["chaos.injected.fail"] == 1
        # the failed first attempt landed on the operator counter
        # (ISSUE 5 satellite: history.archive.failure in metrics)
        j = app.command_handler.handle("metrics")["metrics"]
        assert j["history.archive.failure"]["count"] == 1
    finally:
        chaos.uninstall()
        app.shutdown()


def test_db_commit_failure_rolls_back_cleanly(tmp_path):
    db = Database(str(tmp_path / "t.db"))
    db.initialize()
    chaos.install(ChaosEngine(5, [FaultSpec(
        "db.commit", "io_error", start=0, count=1)]))
    with pytest.raises(ChaosError):
        with db.transaction():
            db.execute("INSERT OR REPLACE INTO storestate "
                       "(statename, state) VALUES ('k', 'v')")
    # rolled back, connection healthy, next commit lands
    assert db.query_one(
        "SELECT state FROM storestate WHERE statename='k'") is None
    with db.transaction():
        db.execute("INSERT OR REPLACE INTO storestate "
                   "(statename, state) VALUES ('k', 'v2')")
    assert db.query_one(
        "SELECT state FROM storestate WHERE statename='k'")[0] == "v2"
    db.close()


def test_completion_fault_surfaces_sticky_error():
    from stellar_core_tpu.ledger.completion import CloseCompletionQueue
    q = CloseCompletionQueue()
    chaos.install(ChaosEngine(6, [FaultSpec(
        "ledger.completion.run", "io_error", start=0, count=1)]))
    ran = []
    q.submit(5, lambda: ran.append(5))
    with pytest.raises(RuntimeError, match="ledger 5"):
        q.join()
    assert ran == []            # the injected fault pre-empted the job


def test_verifier_failure_falls_back_to_native():
    """Device-verifier fault at the txset-validation collection point:
    the herder's lazy batch prevalidator must fall back to the native
    per-signature path and still accept the valid set."""
    pytest.importorskip("jax")
    from stellar_core_tpu.ops.verifier import TpuBatchVerifier
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        import test_standalone_app as m1
        master = m1.master_account(app)
        dest = m1.AppAccount(app, SecretKey.from_seed(b"\x21" * 32))
        m1.submit(app, master.tx(
            [op_create_account(dest.account_id, 10 ** 10)]))
        app.herder.batch_verifier = TpuBatchVerifier(perf=app.perf)
        chaos.install(ChaosEngine(7, [FaultSpec(
            "ops.verifier.batch", "io_error", start=0, count=1 << 30)]))
        # admission warmed the verify cache; the prevalidator only
        # dispatches cache MISSES, so model a remote validator's cold
        # cache to force the device batch (and the injected fault)
        from stellar_core_tpu.crypto.keys import clear_verify_cache
        clear_verify_cache()
        lcl = app.ledger_manager.get_last_closed_ledger_header()
        frame, _, _ = make_tx_set_from_transactions(
            app.herder.tx_queue.get_transactions(), lcl,
            app.config.network_id())
        assert app.herder._check_tx_set_valid(frame) is True
        assert chaos.engine().injected["chaos.injected.io_error"] >= 1
    finally:
        chaos.uninstall()
        app.shutdown()


# ----------------------------------------------------- frozen result pairs --

def test_result_pair_frozen_after_close():
    """The frame actually APPLIED by a close (the one the stored
    TransactionResultPair and any held-back delay-meta reference)
    carries a frozen result: a late in-place mutation that skips
    _reset_result asserts instead of silently corrupting committed
    history."""
    from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    mk = lc.master_key()
    dest = SecretKey.from_seed(b"\x31" * 32)
    tx = lc.make_tx(lm, mk, lc.master_seq(lm) + 1,
                    [op_create_account(lc.xpk(dest), 10 ** 9)])
    lcl = lm.get_last_closed_ledger_header()
    frame, applicable, _ = make_tx_set_from_transactions(
        [tx], lcl, lc.NETWORK_ID)
    applied = applicable.get_txs_in_apply_order()[0]
    value = StellarValue(txSetHash=frame.get_contents_hash(),
                         closeTime=1000)
    lm.close_ledger(LedgerCloseData(2, applicable, value))
    lm.join_completion()
    assert getattr(applied.result, "_frozen", False)
    from stellar_core_tpu.util.checks import AssertionFailed
    from stellar_core_tpu.xdr.results import TransactionResultCode
    with pytest.raises(AssertionFailed, match="closed ledger"):
        applied.set_error(TransactionResultCode.txINTERNAL_ERROR)
    with pytest.raises(AssertionFailed, match="closed ledger"):
        applied.mark_result_failed()
    # a fresh validation pass REPLACES the result and unfreezes
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    with LedgerTxn(lm.root) as ltx:
        applied.check_valid(ltx)
    assert not getattr(applied.result, "_frozen", False)
    applied.set_error(TransactionResultCode.txINTERNAL_ERROR)


# ------------------------------------------------- crash-point matrix --

def _matrix_cfg(base):
    cfg = get_test_config()
    cfg.DATABASE = f"sqlite3://{base}/node.db"
    cfg.BUCKET_DIR_PATH = str(base / "buckets")
    return cfg


def _scheduled_tx(app, seq: int):
    """Deterministic tx for ledger `seq`: a master self-payment whose
    seqNum depends only on `seq` — re-derivable after any rollback."""
    from stellar_core_tpu.tx.frame import make_frame
    from stellar_core_tpu.tx.tx_utils import starting_sequence_number
    key = SecretKey.from_seed(app.config.network_id())
    muxed = MuxedAccount.from_ed25519(key.public_key().raw)
    tx = Transaction(
        sourceAccount=muxed, fee=100,
        seqNum=starting_sequence_number(1) + (seq - 1),
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
    frame = make_frame(env, app.config.network_id())
    sig = key.sign(frame.contents_hash())
    frame.signatures.append(DecoratedSignature(
        hint=key.public_key().hint(), signature=sig))
    env.value.signatures = frame.signatures
    return frame


def _close_seq(app, seq: int) -> None:
    from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
    lm = app.ledger_manager
    frame = _scheduled_tx(app, seq)
    lcl = lm.get_last_closed_ledger_header()
    tx_set, applicable, _ = make_tx_set_from_transactions(
        [frame], lcl, app.config.network_id())
    value = StellarValue(txSetHash=tx_set.get_contents_hash(),
                         closeTime=1000 + seq)
    lm.close_ledger(LedgerCloseData(seq, tx_set, value))
    lm.join_completion()


def _chain_state(app, upto: int):
    rows = app.database.query_all(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders "
        "WHERE ledgerseq <= ? ORDER BY ledgerseq", (upto,))
    from stellar_core_tpu.main.persistent_state import StateEntry
    return ([(r[0], bytes(r[1])) for r in rows],
            app.ledger_manager.get_last_closed_ledger_hash(),
            int(app.persistent_state.get(StateEntry.LAST_CLOSE_COMPLETED)),
            app.history_manager.publish_queue_length())


_TARGET = 6
_CRASH_AT = 4          # close of seq 4 = the 3rd close → hit index 2


def _run_matrix(base, crash_point):
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _matrix_cfg(base))
    app.start()
    seq = 2
    crashed = False
    if crash_point is not None:
        chaos.install(ChaosEngine(8, [FaultSpec(
            crash_point, "crash", start=_CRASH_AT - 2, count=1)]))
    try:
        while seq <= _TARGET:
            try:
                _close_seq(app, seq)
            except SimulatedCrash:
                crashed = True
                break
            except RuntimeError as e:       # deferred-completion crash
                assert isinstance(e.__cause__, SimulatedCrash), e
                crashed = True
                break
            seq += 1
    finally:
        chaos.uninstall()
    if crash_point is None:
        state = _chain_state(app, _TARGET)
        app.shutdown()
        return state
    assert crashed, f"{crash_point} never fired"
    # abandon the crashed app (no shutdown) and restart from its files
    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                              _matrix_cfg(base))
    app2.start()
    try:
        resume = app2.ledger_manager.get_last_closed_ledger_num() + 1
        for s in range(resume, _TARGET + 1):
            _close_seq(app2, s)
        return _chain_state(app2, _TARGET)
    finally:
        app2.shutdown()


@pytest.fixture(scope="module")
def matrix_control(tmp_path_factory):
    return _run_matrix(tmp_path_factory.mktemp("ctl"), None)


@pytest.mark.parametrize("point", CLOSE_CRASH_POINTS)
def test_crash_point_matrix(tmp_path, matrix_control, point):
    """A SimulatedCrash between each adjacent pair of close phases:
    restart recovers through the `lastclosecompleted` path and the
    resumed chain is byte-identical to a crash-free run — same header
    hashes, healed completion marker, consistent publish queue."""
    state = _run_matrix(tmp_path, point)
    assert state[0] == matrix_control[0], "header chain diverged"
    assert state[1] == matrix_control[1]
    assert state[2] == _TARGET          # marker healed to the LCL
    assert state[3] == 0                # publish queue consistent


@pytest.mark.parametrize("tail", ["discarded", "finished"])
@pytest.mark.parametrize("point", ["ledger.close.crash.fees",
                                   "ledger.close.crash.applyTx"])
def test_crash_in_next_close_beside_previous_tail(tmp_path, matrix_control,
                                                  point, tail):
    """What the barrier at the close's first statement made impossible:
    ledger 5 dies in `fees` / `applyTx` while ledger 4's tail has not
    run (the crash loses it: LCL 4, marker 3, healed on restart) or has
    finished (marker 4). Either way nothing of ledger 5 is on disk and
    the resumed chain is the crash-free one."""
    import threading
    from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
    from stellar_core_tpu.main.persistent_state import StateEntry

    def close_no_join(app, seq):
        lm = app.ledger_manager
        lcl = lm.get_last_closed_ledger_header()
        tx_set, _, _ = make_tx_set_from_transactions(
            [_scheduled_tx(app, seq)], lcl, app.config.network_id())
        lm.close_ledger(LedgerCloseData(seq, tx_set, StellarValue(
            txSetHash=tx_set.get_contents_hash(), closeTime=1000 + seq)))

    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _matrix_cfg(tmp_path))
    app.start()
    lm = app.ledger_manager
    _close_seq(app, 2)
    _close_seq(app, 3)
    hold = threading.Event()
    if tail == "discarded":
        # from ledger 4's COMMIT on the worker is busy, so the tail
        # queues behind it and has not started when ledger 5 dies
        lm.closed_hooks.append(
            lambda header, _hash: lm._completion.submit(
                header.ledgerSeq, hold.wait))
    close_no_join(app, _CRASH_AT)
    if tail == "finished":
        lm.join_completion()
    chaos.install(ChaosEngine(8, [FaultSpec(point, "crash")]))
    try:
        with pytest.raises(SimulatedCrash):
            close_no_join(app, _CRASH_AT + 1)
    finally:
        chaos.uninstall()
    lm.discard_pending_completion()     # as Simulation.crash_node does
    hold.set()
    lm.join_completion()
    # what the dead process left in its files
    db = app.database
    assert db.query_one(
        "SELECT MAX(ledgerseq) FROM ledgerheaders")[0] == _CRASH_AT
    assert int(app.persistent_state.get(StateEntry.LAST_CLOSE_COMPLETED)) \
        == (_CRASH_AT - 1 if tail == "discarded" else _CRASH_AT)

    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                              _matrix_cfg(tmp_path))
    app2.start()
    try:
        assert app2.ledger_manager.get_last_closed_ledger_num() == _CRASH_AT
        assert int(app2.persistent_state.get(
            StateEntry.LAST_CLOSE_COMPLETED)) == _CRASH_AT
        rows = app2.database.query_one(
            "SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?",
            (_CRASH_AT,))[0]
        assert rows == (0 if tail == "discarded" else 1)
        for s in range(_CRASH_AT + 1, _TARGET + 1):
            _close_seq(app2, s)
        state = _chain_state(app2, _TARGET)
        assert state[0] == matrix_control[0], "header chain diverged"
        assert state[1:] == (matrix_control[1], _TARGET, 0)
    finally:
        app2.shutdown()


@pytest.mark.parametrize("died", ["ledger.close.crash.queued",
                                  "tail pending", "tail at its marker"])
def test_crash_between_manual_close_commit_and_tail_is_reported(
        tmp_path, caplog, died):
    """A manual close returns at the commit (PR 34), so the process can
    die with ledger 4 durable and its tail not run: before the tail was
    queued (the crash point after COMMIT), while it waits on the worker,
    or inside it before the marker. The restart gap-check finds
    LAST_CLOSE_COMPLETED behind the LCL, says so, drops the partial
    tail, heals the marker, and the node keeps closing."""
    import logging
    import threading
    from stellar_core_tpu.main.persistent_state import StateEntry

    def manual_close(app, seq):
        assert app.herder.recv_transaction(_scheduled_tx(app, seq)).name \
            == "ADD_STATUS_PENDING"
        app.manual_close()

    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _matrix_cfg(tmp_path))
    app.start()
    lm = app.ledger_manager
    manual_close(app, 2)
    manual_close(app, 3)
    app.herder.join_completion()
    hold = threading.Event()
    if died == "tail pending":
        # the worker is busy from ledger 4's COMMIT on: its tail queues
        lm.closed_hooks.append(
            lambda header, _hash: lm._completion.submit(
                header.ledgerSeq, hold.wait))
    point = died if died.startswith("ledger.") \
        else "ledger.close.crash.complete.meta"
    chaos.install(ChaosEngine(8, [FaultSpec(point, "crash")]))
    try:
        if died == "ledger.close.crash.queued":
            with pytest.raises(SimulatedCrash):
                manual_close(app, _CRASH_AT)
        else:
            manual_close(app, _CRASH_AT)    # returns: ledger 4 committed
            if died == "tail at its marker":
                with pytest.raises(RuntimeError) as failure:
                    app.herder.join_completion()
                assert isinstance(failure.value.__cause__, SimulatedCrash)
    finally:
        chaos.uninstall()
    assert lm.get_last_closed_ledger_num() == _CRASH_AT
    lcl_hash = lm.get_last_closed_ledger_hash()
    lm.discard_pending_completion()     # as Simulation.crash_node does
    hold.set()
    lm.join_completion(reraise=False)
    assert app.database._conn.execute(
        "SELECT state FROM storestate WHERE statename=?",
        (StateEntry.LAST_CLOSE_COMPLETED.value,)).fetchone()[0] \
        == str(_CRASH_AT - 1)

    with caplog.at_level(logging.WARNING):
        app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                                  _matrix_cfg(tmp_path))
        app2.start()
    try:
        assert any(f"crash mid-completion: ledgers {_CRASH_AT}..{_CRASH_AT}"
                   in r.getMessage() for r in caplog.records)
        lm2 = app2.ledger_manager
        assert lm2.get_last_closed_ledger_num() == _CRASH_AT
        assert lm2.get_last_closed_ledger_hash() == lcl_hash
        assert int(app2.persistent_state.get(
            StateEntry.LAST_CLOSE_COMPLETED)) == _CRASH_AT
        assert app2.database.query_one(
            "SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?",
            (_CRASH_AT,))[0] == 0
        manual_close(app2, _CRASH_AT + 1)
        assert app2.database.query_one(
            "SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?",
            (_CRASH_AT + 1,))[0] == 1
    finally:
        app2.shutdown()


@pytest.mark.parametrize("crash_point", ["ledger.close.crash.commit",
                                         "ledger.close.crash.queued"])
def test_publish_queue_survives_crash_after_queueing(tmp_path,
                                                     crash_point):
    """Crash on either side of the checkpoint close's COMMIT (the row
    rides the close transaction, so even a kill immediately after
    COMMIT — before in-memory adoption — keeps it): the durable
    publish queue re-queues it on restart with the queue-time HAS, and
    the retried publish lands in the archive."""
    root = str(tmp_path / "archive")
    cfg = _matrix_cfg(tmp_path)
    cfg.HISTORY = {"t": {
        "get": f"cp {root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {root}/{{1}}) && cp {{0}} "
               f"{root}/{{1}}",
    }}
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    # crash at a post-COMMIT boundary of the checkpoint close (seq 63)
    chaos.install(ChaosEngine(9, [FaultSpec(
        crash_point, "crash", start=61, count=1)]))
    try:
        seq = 2
        while True:
            try:
                _close_seq(app, seq)
            except SimulatedCrash:
                break
            seq += 1
        assert seq == 63
    finally:
        chaos.uninstall()
    # the queue row is durable even though the node never published
    assert app.database.query_one(
        "SELECT ledgerseq FROM publishqueue")[0] == 63
    assert app.history_manager.published_count == 0

    cfg2 = _matrix_cfg(tmp_path)
    cfg2.HISTORY = cfg.HISTORY
    app2 = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg2)
    app2.start()
    try:
        hm = app2.history_manager
        assert hm.publish_queue_length() == 1
        assert hm._publish_queue[0].seq == 63
        assert hm.queued_bucket_hashes()      # GC keeps its buckets
        assert hm.publish_queued_history() == 1
        with open(os.path.join(
                root, ".well-known/stellar-history.json")) as f:
            assert json.load(f)["currentLedger"] == 63
        assert app2.database.query_one(
            "SELECT COUNT(*) FROM publishqueue")[0] == 0
    finally:
        app2.shutdown()


# ------------------------------------------------- seal zone split --

def test_seal_zone_children_emitted(tmp_path):
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             _matrix_cfg(tmp_path))
    app.start()
    try:
        _close_seq(app, 2)
        report = app.perf.report()
        for zone in ("ledger.close.seal", "ledger.close.seal.sql",
                     "ledger.close.seal.fsync"):
            assert zone in report, f"missing {zone}"
        assert report["ledger.close.seal"]["total_ms"] >= \
            report["ledger.close.seal.sql"]["total_ms"]
    finally:
        app.shutdown()


# ------------------------------------------------- multinode scenario --

def test_multinode_chaos_scenario_converges(tmp_path):
    """The acceptance scenario: ≥5 fault classes under one seeded
    schedule; survivors stay live, their header chains are
    byte-identical to the fault-free run, the whole run reproduces
    from its seed (schedule run twice → same faults, same hashes),
    and node 0's circuit breaker rides the device-outage window
    (ISSUE 5): trips OPEN after the failure threshold, makes ZERO
    device dispatch attempts while OPEN, probes HALF_OPEN on the
    backoff schedule, and re-closes once the window exhausts."""
    from stellar_core_tpu.simulation.chaos import run_scenario
    res = run_scenario(seed=6, target=10,
                       archive_dir=str(tmp_path / "archive"))
    assert res["liveness_ok"], res
    assert res["safety_ok"], res
    assert res["repro_ok"], res
    assert res["archive_ok"], res
    assert len(res["crashed"]) == 1
    assert len(res["survivors"]) == 3
    classes = set(res["fault_classes"])
    assert {"drop", "reorder", "corrupt", "crash", "io_error",
            "fail"} <= classes
    assert res["archive_retry"]["ok"]
    # every survivor served a valid clusterstatus snapshot (ISSUE 8:
    # the structured health document the multi-process harness reads)
    assert res["clusterstatus_ok"], res["clusterstatus"]
    assert len(res["clusterstatus"]) == 3
    # breaker evidence (ISSUE 5 acceptance)
    assert res["breaker_ok"], res["breaker"]
    b = res["breaker"]
    assert b["tripped"] and b["probed"] and b["reclosed"]
    assert b["quiet_while_open"]           # dispatch counter frozen
    assert b["skips"] > 0                  # degraded-mode traffic ran
    moves = [(t["from"], t["to"]) for t in b["transitions"]]
    assert moves[0] == ("CLOSED", "OPEN")
    assert ("OPEN", "HALF_OPEN") in moves
    assert moves[-1] == ("HALF_OPEN", "CLOSED")


@pytest.mark.slow
@pytest.mark.soak
def test_chaos_convergence_soak(tmp_path):
    """Longer randomized-but-seeded soak: every seed must converge."""
    from stellar_core_tpu.simulation.chaos import run_scenario
    for i in range(3):
        res = run_scenario(seed=1000 + i, target=10,
                           archive_dir=str(tmp_path / f"archive-{i}"))
        assert res["liveness_ok"] and res["safety_ok"] \
            and res["repro_ok"], res
