"""History/catchup acceptance tier.

The CatchupSimulation matrix (reference:
history/test/HistoryTestsUtils.h:52-95 — publish checkpoints, catch up
new nodes across modes): minimal / complete / recent, a mid-history
PROTOCOL UPGRADE every replay must cross, trailing ("online"-style)
re-catchup against a moving archive, flaky-archive retries, and
corrupted-archive failure.
"""

import glob
import gzip
import os

import pytest

import test_standalone_app as m1
from txtest_utils import op_create_account, op_payment

from stellar_core_tpu.catchup.catchup_work import (CATCHUP_MINIMAL,
                                                   CatchupConfiguration,
                                                   CatchupWork)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.upgrades import UpgradeParameters
from stellar_core_tpu.history.archive import make_tmpdir_archive
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work import run_work_to_completion
from stellar_core_tpu.work.basic_work import State

UPGRADE_AT = 40          # ledger where the protocol bump externalizes
START_PROTO = 20
END_PROTO = 21


def _publish_with_upgrade(tmp_path, n_ledgers=130):
    """Standalone publisher that starts on protocol 20, upgrades to 21
    mid-history, and closes payments before and after the bump."""
    archive_root = str(tmp_path / "archive")
    cfg = get_test_config()
    cfg.LEDGER_PROTOCOL_VERSION = START_PROTO   # genesis protocol
    cfg.HISTORY = {"test": {
        "get": f"cp {archive_root}/{{0}} {{1}}",
        "put": f"mkdir -p $(dirname {archive_root}/{{1}}) && "
               f"cp {{0}} {archive_root}/{{1}}",
    }}
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    master = m1.master_account(app)
    dests = [m1.AppAccount(app, SecretKey.from_seed(bytes([i]) * 32))
             for i in range(1, 5)]
    for d in dests:
        m1.submit(app, master.tx([op_create_account(d.account_id,
                                                    10**12)]))
    app.manual_close()
    for d in dests:
        d.sync_seq()
    lcl = app.ledger_manager.get_last_closed_ledger_num()
    while lcl < n_ledgers:
        if lcl == UPGRADE_AT - 1:
            app.herder.upgrades.set_parameters(UpgradeParameters(
                upgrade_time=0, protocol_version=END_PROTO))
        if lcl % 5 == 0:
            d = dests[lcl % len(dests)]
            m1.submit(app, d.tx([op_payment(master.muxed, 1000)]))
        app.manual_close()
        lcl = app.ledger_manager.get_last_closed_ledger_num()
    hdr = app.ledger_manager.get_last_closed_ledger_header()
    assert hdr.ledgerVersion == END_PROTO, \
        "publisher never crossed the protocol upgrade"
    app.herder.join_completion()    # who reads the archive joins the tail
    return app, make_tmpdir_archive("test", archive_root), archive_root


def _fresh_node(app_a, **cfg_overrides):
    cfg = get_test_config()
    cfg.NETWORK_PASSPHRASE = app_a.config.NETWORK_PASSPHRASE
    cfg.LEDGER_PROTOCOL_VERSION = START_PROTO   # genesis protocol
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def _chain_hash(app, seq):
    row = app.database.query_one(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=?", (seq,))
    return bytes(row[0])


@pytest.mark.parametrize("mode,count", [
    ("complete", 0xFFFFFFFF),
    ("minimal", CATCHUP_MINIMAL),
    ("recent", 16),
])
def test_catchup_modes_across_protocol_upgrade(tmp_path, mode, count):
    """Every catchup mode lands on the publisher's post-upgrade chain:
    the replay (or bucket apply) must reproduce ledgers closed under
    BOTH protocol versions."""
    app_a, archive, _root = _publish_with_upgrade(tmp_path)
    try:
        tip = 127
        hash_a = _chain_hash(app_a, tip)
        app_b = _fresh_node(app_a)
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0,
                                                    count=count))
            assert run_work_to_completion(
                app_b, work, timeout_virtual=4000) == State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == tip
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                hash_a
            hdr = app_b.ledger_manager.get_last_closed_ledger_header()
            assert hdr.ledgerVersion == END_PROTO
            bal_a = m1.app_account_entry(
                app_a, m1.master_account(app_a).account_id).balance
            bal_b = m1.app_account_entry(
                app_b, m1.master_account(app_b).account_id).balance
            assert bal_a == bal_b
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_trailing_catchup_against_moving_archive(tmp_path):
    """The 'online' leg: a caught-up node falls behind while the
    publisher keeps closing; a second catchup brings it to the new
    tip (reference: CatchupSimulation::catchupOnline re-runs)."""
    app_a, archive, _root = _publish_with_upgrade(tmp_path, n_ledgers=130)
    try:
        app_b = _fresh_node(app_a)
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(
                app_b, work, timeout_virtual=4000) == State.WORK_SUCCESS
            first_tip = app_b.ledger_manager.get_last_closed_ledger_num()
            assert first_tip == 127

            # the network moves on: publish two more checkpoints
            master = m1.master_account(app_a)
            lcl = app_a.ledger_manager.get_last_closed_ledger_num()
            while lcl < 260:
                if lcl % 6 == 0:
                    m1.submit(app_a, master.tx(
                        [op_payment(master.muxed, 1)]))
                app_a.manual_close()
                lcl = app_a.ledger_manager.get_last_closed_ledger_num()

            work2 = CatchupWork(app_b, archive,
                                CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(
                app_b, work2, timeout_virtual=6000) == State.WORK_SUCCESS
            tip2 = app_b.ledger_manager.get_last_closed_ledger_num()
            assert tip2 == 255
            assert app_b.ledger_manager.get_last_closed_ledger_hash() == \
                _chain_hash(app_a, tip2)
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_survives_flaky_archive(tmp_path):
    """Every `get` fails on its first attempt; BasicWork's retry policy
    (reference: BasicWork.h RETRY_* + GetRemoteFileWork retries) must
    carry catchup to success anyway."""
    app_a, archive, root = _publish_with_upgrade(tmp_path, n_ledgers=66)
    try:
        marker_dir = str(tmp_path / "flaky-markers")
        os.makedirs(marker_dir, exist_ok=True)
        # fail each file's first fetch: marker file distinguishes tries
        archive.get_cmd = (
            f"sh -c 'm={marker_dir}/$(echo {{0}} | tr / _); "
            f"if [ ! -f $m ]; then touch $m; exit 1; fi; "
            f"cp {root}/{{0}} {{1}}'")
        app_b = _fresh_node(app_a)
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            assert run_work_to_completion(
                app_b, work, timeout_virtual=8000) == State.WORK_SUCCESS
            assert app_b.ledger_manager.get_last_closed_ledger_num() == 63
            assert os.listdir(marker_dir), "flaky gate never triggered"
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()


def test_catchup_rejects_corrupted_archive(tmp_path):
    """A corrupted transactions file must fail catchup cleanly (hash /
    replay divergence detected), never externalize a wrong ledger."""
    app_a, archive, root = _publish_with_upgrade(tmp_path, n_ledgers=66)
    try:
        tx_files = sorted(glob.glob(
            os.path.join(root, "transactions", "**", "*.xdr.gz"),
            recursive=True))
        assert tx_files
        raw = gzip.decompress(open(tx_files[-1], "rb").read())
        if len(raw) > 40:
            raw = raw[:-20] + bytes([raw[-20] ^ 0xFF]) + raw[-19:]
        else:
            raw = raw + b"\x01"
        with open(tx_files[-1], "wb") as f:
            f.write(gzip.compress(raw))
        app_b = _fresh_node(app_a)
        try:
            work = CatchupWork(app_b, archive,
                               CatchupConfiguration(to_ledger=0))
            final = run_work_to_completion(app_b, work,
                                           timeout_virtual=8000)
            if final == State.WORK_SUCCESS:
                # corruption in the last checkpoint may leave earlier
                # ledgers valid — but the replayed chain must never
                # diverge from the publisher's
                tip = app_b.ledger_manager.get_last_closed_ledger_num()
                assert app_b.ledger_manager \
                    .get_last_closed_ledger_hash() == \
                    _chain_hash(app_a, tip)
            else:
                assert final == State.WORK_FAILURE
        finally:
            app_b.shutdown()
    finally:
        app_a.shutdown()
