"""Simulation + Topologies + LoadGenerator tests (reference:
simulation-driven suites like HerderTests/CoreTests: whole networks
cranked deterministically on virtual time)."""

import pytest

from stellar_core_tpu.simulation import LoadGenerator, Simulation, topologies


def test_pair_reaches_consensus():
    sim = topologies.pair()
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(3))
        assert sim.ledger_hashes_agree(2)
        assert sim.ledger_hashes_agree(3)
    finally:
        sim.stop_all_nodes()


def test_core4_with_load():
    sim = topologies.core(4)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(2))
        app = sim.apps()[0]
        lg = LoadGenerator(app)
        assert lg.generate_accounts(10) == 10
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        lg.sync_account_seqs()
        assert lg.generate_payments(20) == 20
        target = app.ledger_manager.get_last_closed_ledger_num() + 2
        assert sim.crank_until(lambda: sim.have_all_externalized(target))
        # the payments landed identically everywhere
        seq = min(a.ledger_manager.get_last_closed_ledger_num()
                  for a in sim.apps())
        assert sim.ledger_hashes_agree(seq)
        assert lg.failed == 0
    finally:
        sim.stop_all_nodes()


def test_cycle6_converges():
    """Ring quorums: every node trusts its neighbours; the whole ring
    still converges on one chain."""
    sim = topologies.cycle(6)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(3),
                               timeout_virtual_seconds=300)
        assert sim.ledger_hashes_agree(2)
    finally:
        sim.stop_all_nodes()


def test_hierarchical_outer_follows_core():
    sim = topologies.hierarchical_quorum(3, 2)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(2),
                               timeout_virtual_seconds=300)
        assert sim.ledger_hashes_agree(2)
    finally:
        sim.stop_all_nodes()


def test_continuous_operation_many_ledgers():
    """The network keeps closing ledgers on cadence without drift."""
    sim = topologies.core(3)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(10),
                               timeout_virtual_seconds=300)
        assert sim.ledger_hashes_agree(10)
    finally:
        sim.stop_all_nodes()


def test_loadgen_pretend_mixed_soroban_modes():
    """PRETEND / MIXED_CLASSIC / SOROBAN-upload loadgen modes (reference:
    LoadGenerator.h:28-35, LoadGenerator.cpp:469-494) drive a standalone
    manual-close app end to end."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    with Application.create(clock, cfg) as app:
        app.start()
        lg = LoadGenerator(app)
        assert lg.generate_accounts(4) == 4
        app.manual_close()
        lg.sync_account_seqs()

        assert lg.generate_pretend(6) == 6
        app.manual_close()

        assert lg.setup_dex() == 4
        app.manual_close()
        assert lg.generate_mixed(10, dex_percent=50) == 10
        app.manual_close()
        # the blend really is mixed: ~half the txs rested offers on the
        # book and the rest were payments
        row = app.database.query_one("SELECT COUNT(*) FROM offers", ())
        assert row[0] == 5

        assert lg.generate_soroban_uploads(3) == 3
        app.manual_close()
        row = app.database.query_one(
            "SELECT COUNT(*) FROM contractcode", ())
        assert row[0] >= 3
        assert lg.failed == 0


def test_loadgen_sac_and_invoke_modes():
    """SAC-transfer + contract-invoke loadgen: the
    measured workloads exercise the wasm VM and the built-in SAC."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    with Application.create(clock, cfg) as app:
        app.start()
        lg = LoadGenerator(app)
        assert lg.generate_accounts(4) == 4
        app.manual_close()
        lg.sync_account_seqs()

        cid = lg.setup_sac()
        app.manual_close()
        lg.sync_account_seqs()
        before = [app_balance(app, a) for a in lg.accounts]
        assert lg.generate_sac_transfers(cid, 4, amount=1000) == 4
        app.manual_close()
        lg.sync_account_seqs()
        # every account sent 1000 and received 1000, minus its fee;
        # balances moved => the SAC transfers really applied
        after = [app_balance(app, a) for a in lg.accounts]
        assert all(b != a for a, b in zip(before, after))
        assert lg.failed == 0

        ccid = lg.setup_counter_contract()
        app.manual_close()
        lg.sync_account_seqs()
        assert lg.generate_counter_invokes(ccid, 5) == 5
        app.manual_close()
        from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
        from stellar_core_tpu.xdr import contract as cx
        from stellar_core_tpu.xdr.ledger_entries import LedgerKey
        addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                            ccid)
        with LedgerTxn(app.ledger_manager.root) as ltx:
            le = ltx.load_without_record(LedgerKey.contract_data(
                addr, cx.SCVal(cx.SCValType.SCV_SYMBOL, b"count"),
                cx.ContractDataDurability.PERSISTENT))
            assert le is not None and le.data.value.val.value == 5
        assert lg.failed == 0


def app_balance(app, acct):
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.xdr.ledger_entries import LedgerKey
    with LedgerTxn(app.ledger_manager.root) as ltx:
        return ltx.load_without_record(
            LedgerKey.account(acct.account_id)).data.value.balance
