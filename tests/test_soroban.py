"""Soroban host layer tests: upload → create → invoke through real
transactions against a standalone node; storage, TTL, auth, events,
budget, fees (reference behavior: InvokeHostFunctionOpFrame +
soroban-env-host e2e_invoke surface)."""

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.main import Application, get_test_config
from stellar_core_tpu.soroban import scvm
from stellar_core_tpu.soroban.host import (contract_id_from_preimage,
                                           instance_key,
                                           soroban_auth_payload,
                                           ttl_key_for)
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr import contract as cx
from stellar_core_tpu.xdr.ledger_entries import LedgerKey
from stellar_core_tpu.xdr.transaction import (Memo, MemoType, MuxedAccount,
                                              Operation, _OperationBody,
                                              OperationType, Preconditions,
                                              PreconditionType, Transaction,
                                              TransactionEnvelope,
                                              TransactionV1Envelope, _TxExt,
                                              DecoratedSignature)
from stellar_core_tpu.xdr.types import EnvelopeType, PublicKey

import test_standalone_app as m1

RESOURCE_FEE = 10_000_000


@pytest.fixture(params=["scvm", "wasm"])
def app(request):
    """Each test runs twice: once against the builtin scvm build of the
    counter contract, once against the real-wasm build of the same
    logic (soroban/scvm_wasm.py compiler → soroban/wasm interpreter)."""
    global COUNTER_CODE
    COUNTER_CODE = CODE_BUILDS[request.param]
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    with Application.create(clock, cfg) as a:
        a.start()
        yield a


def soroban_tx(app, source, op_body, footprint_ro, footprint_rw,
               instructions=2_000_000, read=10000, write=10000,
               resource_fee=RESOURCE_FEE):
    sd = cx.SorobanTransactionData(
        resources=cx.SorobanResources(
            footprint=cx.LedgerFootprint(readOnly=footprint_ro,
                                         readWrite=footprint_rw),
            instructions=instructions, readBytes=read, writeBytes=write),
        resourceFee=resource_fee)
    source.seq += 1
    tx = Transaction(
        sourceAccount=source.muxed, fee=100 + resource_fee,
        seqNum=source.seq,
        cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE),
        operations=[Operation(sourceAccount=None, body=op_body)],
        ext=_TxExt(1, sd))
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    from stellar_core_tpu.tx.frame import make_frame
    frame = make_frame(env, app.config.network_id())
    sig = source.key.sign(frame.contents_hash())
    frame.signatures.append(DecoratedSignature(
        hint=source.key.public_key().hint(), signature=sig))
    env.value.signatures = frame.signatures
    return frame


def submit_and_close(app, frame):
    r = m1.submit(app, frame)
    assert r["status"] == "PENDING", r
    app.manual_close()
    row = app.database.query_one(
        "SELECT txresult FROM txhistory WHERE txid=?", (frame.full_hash(),))
    assert row is not None, "tx not applied"
    from stellar_core_tpu.xdr.results import TransactionResultPair
    return TransactionResultPair.from_bytes(bytes(row[0]))


COUNTER_FUNCTIONS = {
    "increment": scvm.op(
        scvm.sym("seq"),
        scvm.op(scvm.sym("put"), scvm.op(scvm.sym("lit"), scvm.sym("count")),
                scvm.op(scvm.sym("add"),
                        scvm.op(scvm.sym("if"),
                                scvm.op(scvm.sym("eq"),
                                        scvm.op(scvm.sym("get"),
                                                scvm.op(scvm.sym("lit"),
                                                        scvm.sym("count"))),
                                        cx.SCVal(cx.SCValType.SCV_VOID)),
                                scvm.u64(0),
                                scvm.op(scvm.sym("get"),
                                        scvm.op(scvm.sym("lit"),
                                                scvm.sym("count")))),
                        scvm.u64(1))),
        scvm.op(scvm.sym("get"), scvm.op(scvm.sym("lit"),
                                         scvm.sym("count")))),
    "get_count": scvm.op(scvm.sym("get"),
                         scvm.op(scvm.sym("lit"), scvm.sym("count"))),
    "auth_bump": scvm.op(
        scvm.sym("seq"),
        scvm.op(scvm.sym("require_auth"), scvm.op(scvm.sym("arg"),
                                                  scvm.u64(0))),
        scvm.op(scvm.sym("event"),
                scvm.op(scvm.sym("lit"), scvm.sym("bumped")),
                scvm.u64(1))),
    "boom": scvm.op(scvm.sym("fail")),
}

# scvm-only extension (the scvm_wasm compiler has no `log` mapping):
# used by the diagnostic-events test via an scvm build
NOISY_FUNCTIONS = dict(COUNTER_FUNCTIONS)
NOISY_FUNCTIONS["noisy"] = scvm.op(
    scvm.sym("seq"),
    scvm.op(scvm.sym("log"), scvm.op(scvm.sym("lit"),
                                     scvm.sym("hello-diag"))),
    scvm.u64(1))
NOISY_FUNCTIONS["noisy_boom"] = scvm.op(
    scvm.sym("seq"),
    scvm.op(scvm.sym("log"), scvm.op(scvm.sym("lit"),
                                     scvm.sym("hello-diag"))),
    scvm.op(scvm.sym("fail")))

from stellar_core_tpu.soroban.scvm_wasm import make_wasm_code  # noqa: E402

CODE_BUILDS = {"scvm": scvm.make_code(COUNTER_FUNCTIONS),
               "wasm": make_wasm_code(COUNTER_FUNCTIONS)}
COUNTER_CODE = CODE_BUILDS["scvm"]


def wasm_hash():
    return sha256(COUNTER_CODE)


def upload_op():
    return _OperationBody(
        OperationType.INVOKE_HOST_FUNCTION,
        cx.InvokeHostFunctionOp(hostFunction=cx.HostFunction(
            cx.HostFunctionType.HOST_FUNCTION_TYPE_UPLOAD_CONTRACT_WASM,
            COUNTER_CODE), auth=[]))


def create_op(app, master):
    preimage = cx.ContractIDPreimage(
        cx.ContractIDPreimageType.CONTRACT_ID_PREIMAGE_FROM_ADDRESS,
        cx._ContractIDPreimageFromAddress(
            address=cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                                 master.account_id),
            salt=b"\x01" * 32))
    cid = contract_id_from_preimage(app.config.network_id(), preimage)
    body = _OperationBody(
        OperationType.INVOKE_HOST_FUNCTION,
        cx.InvokeHostFunctionOp(hostFunction=cx.HostFunction(
            cx.HostFunctionType.HOST_FUNCTION_TYPE_CREATE_CONTRACT,
            cx.CreateContractArgs(
                contractIDPreimage=preimage,
                executable=cx.ContractExecutable(
                    cx.ContractExecutableType.CONTRACT_EXECUTABLE_WASM,
                    wasm_hash()))), auth=[
                        cx.SorobanAuthorizationEntry(
                            credentials=cx.SorobanCredentials(
                                cx.SorobanCredentialsType
                                .SOROBAN_CREDENTIALS_SOURCE_ACCOUNT),
                            rootInvocation=cx.SorobanAuthorizedInvocation(
                                function=cx.SorobanAuthorizedFunction(
                                    cx.SorobanAuthorizedFunctionType
                                    .SOROBAN_AUTHORIZED_FUNCTION_TYPE_CREATE_CONTRACT_HOST_FN,
                                    cx.CreateContractArgs(
                                        contractIDPreimage=preimage,
                                        executable=cx.ContractExecutable(
                                            cx.ContractExecutableType
                                            .CONTRACT_EXECUTABLE_WASM,
                                            wasm_hash()))),
                                subInvocations=[]))]))
    return body, cid


def invoke_op(cid, fn, args=()):
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    return _OperationBody(
        OperationType.INVOKE_HOST_FUNCTION,
        cx.InvokeHostFunctionOp(hostFunction=cx.HostFunction(
            cx.HostFunctionType.HOST_FUNCTION_TYPE_INVOKE_CONTRACT,
            cx.InvokeContractArgs(
                contractAddress=addr,
                functionName=fn.encode(),
                args=list(args))), auth=[]))


def counter_key(cid):
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    return LedgerKey.contract_data(
        addr, cx.SCVal(cx.SCValType.SCV_SYMBOL, b"count"),
        cx.ContractDataDurability.PERSISTENT)


def deploy(app):
    """upload + create; returns (master, contract id)."""
    master = m1.master_account(app)
    code_key = LedgerKey.contract_code(wasm_hash())
    res = submit_and_close(app, soroban_tx(
        app, master, upload_op(), [], [code_key]))
    assert res.result.result.disc.name == "txSUCCESS", res
    body, cid = create_op(app, master)
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    res = submit_and_close(app, soroban_tx(
        app, master, body, [code_key], [instance_key(addr)]))
    assert res.result.result.disc.name == "txSUCCESS", res
    return master, cid


def invoke_footprints(cid):
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    ro = [LedgerKey.contract_code(wasm_hash()), instance_key(addr)]
    rw = [counter_key(cid)]
    return ro, rw


def test_upload_create_invoke_counter(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    for expected in (1, 2, 3):
        res = submit_and_close(app, soroban_tx(
            app, master, invoke_op(cid, "increment"), ro, rw))
        assert res.result.result.disc.name == "txSUCCESS", res
    # read back through the ledger
    with LedgerTxn(app.ledger_manager.root) as ltx:
        le = ltx.load_without_record(counter_key(cid))
        assert le is not None
        assert le.data.value.val.value == 3
        # TTL entry exists and is live
        ttl = ltx.load_without_record(ttl_key_for(counter_key(cid)))
        assert ttl is not None
        assert ttl.data.value.liveUntilLedgerSeq > \
            app.ledger_manager.get_last_closed_ledger_num()


def test_contract_trap_fails_tx(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    res = submit_and_close(app, soroban_tx(
        app, master, invoke_op(cid, "boom"), ro, rw))
    assert res.result.result.disc.name == "txFAILED"


def test_write_outside_footprint_fails(app):
    master, cid = deploy(app)
    ro, _ = invoke_footprints(cid)
    # no read-write footprint for the counter key → storage error
    res = submit_and_close(app, soroban_tx(
        app, master, invoke_op(cid, "increment"), ro, []))
    assert res.result.result.disc.name == "txFAILED"


def test_budget_exhaustion(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    res = submit_and_close(app, soroban_tx(
        app, master, invoke_op(cid, "increment"), ro, rw,
        instructions=200))  # far below the storage-op costs
    assert res.result.result.disc.name == "txFAILED"


def test_source_account_auth_and_event(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    addr_val = cx.SCVal(
        cx.SCValType.SCV_ADDRESS,
        cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                     master.account_id))
    body = invoke_op(cid, "auth_bump", [addr_val])
    # add source-account credentials
    body.value.auth = [cx.SorobanAuthorizationEntry(
        credentials=cx.SorobanCredentials(
            cx.SorobanCredentialsType.SOROBAN_CREDENTIALS_SOURCE_ACCOUNT),
        rootInvocation=cx.SorobanAuthorizedInvocation(
            function=cx.SorobanAuthorizedFunction(
                cx.SorobanAuthorizedFunctionType
                .SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN,
                cx.InvokeContractArgs(
                    contractAddress=cx.SCAddress(
                        cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid),
                    functionName=b"auth_bump", args=[addr_val])),
            subInvocations=[]))]
    res = submit_and_close(app, soroban_tx(app, master, body, ro, rw))
    assert res.result.result.disc.name == "txSUCCESS", res


def test_missing_auth_fails(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    other = SecretKey.from_seed(b"\x55" * 32)
    addr_val = cx.SCVal(
        cx.SCValType.SCV_ADDRESS,
        cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                     PublicKey.ed25519(other.public_key().raw)))
    res = submit_and_close(app, soroban_tx(
        app, master, invoke_op(cid, "auth_bump", [addr_val]), ro, rw))
    assert res.result.result.disc.name == "txFAILED"


def test_soroban_tx_structural_validation(app):
    """Multi-op soroban txs and missing sorobanData are rejected at
    admission (reference: txMALFORMED)."""
    master = m1.master_account(app)
    body = upload_op()
    master.seq += 1
    tx = Transaction(
        sourceAccount=master.muxed, fee=100 + RESOURCE_FEE,
        seqNum=master.seq,
        cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE),
        operations=[Operation(sourceAccount=None, body=body)],
        ext=_TxExt(0))  # missing sorobanData
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    from stellar_core_tpu.tx.frame import make_frame
    frame = make_frame(env, app.config.network_id())
    sig = master.key.sign(frame.contents_hash())
    frame.signatures.append(DecoratedSignature(
        hint=master.key.public_key().hint(), signature=sig))
    env.value.signatures = frame.signatures
    r = m1.submit(app, frame)
    assert r["status"] == "ERROR"


def test_extend_and_restore_ttl(app):
    master, cid = deploy(app)
    ro, rw = invoke_footprints(cid)
    submit_and_close(app, soroban_tx(
        app, master, invoke_op(cid, "increment"), ro, rw))
    key = counter_key(cid)
    with LedgerTxn(app.ledger_manager.root) as ltx:
        before = ltx.load_without_record(
            ttl_key_for(key)).data.value.liveUntilLedgerSeq

    # extend the TTL via the op
    body = _OperationBody(
        OperationType.EXTEND_FOOTPRINT_TTL,
        cx.ExtendFootprintTTLOp(extendTo=50_000))
    res = submit_and_close(app, soroban_tx(
        app, master, body, [key], []))
    assert res.result.result.disc.name == "txSUCCESS", res
    with LedgerTxn(app.ledger_manager.root) as ltx:
        after = ltx.load_without_record(
            ttl_key_for(key)).data.value.liveUntilLedgerSeq
    assert after > before

    # simulate archival, then restore
    with LedgerTxn(app.ledger_manager.root) as ltx:
        ttl_le = ltx.load(ttl_key_for(key))
        ttl_le.data.value.liveUntilLedgerSeq = 1
        ltx.commit()
    body = _OperationBody(
        OperationType.RESTORE_FOOTPRINT,
        cx.RestoreFootprintOp())
    res = submit_and_close(app, soroban_tx(app, master, body, [], [key]))
    assert res.result.result.disc.name == "txSUCCESS", res
    with LedgerTxn(app.ledger_manager.root) as ltx:
        restored = ltx.load_without_record(
            ttl_key_for(key)).data.value.liveUntilLedgerSeq
    assert restored > app.ledger_manager.get_last_closed_ledger_num()


def test_fee_model_sanity():
    from stellar_core_tpu.soroban.fees import (
        compute_transaction_resource_fee, compute_write_fee_per_1kb)
    from stellar_core_tpu.soroban.network_config import initial_settings

    class _Cfg:
        pass
    from stellar_core_tpu.xdr.contract import ConfigSettingID
    settings = {s.disc: s.value for s in initial_settings()}
    cfg = _Cfg()
    cfg.fee_rate_per_instructions_increment = settings[
        ConfigSettingID.CONFIG_SETTING_CONTRACT_COMPUTE_V0]\
        .feeRatePerInstructionsIncrement
    cfg.ledger_cost = settings[
        ConfigSettingID.CONFIG_SETTING_CONTRACT_LEDGER_COST_V0]
    cfg.bandwidth = settings[
        ConfigSettingID.CONFIG_SETTING_CONTRACT_BANDWIDTH_V0]
    cfg.historical = settings[
        ConfigSettingID.CONFIG_SETTING_CONTRACT_HISTORICAL_DATA_V0]
    cfg.events_cfg = settings[
        ConfigSettingID.CONFIG_SETTING_CONTRACT_EVENTS_V0]

    res = cx.SorobanResources(
        footprint=cx.LedgerFootprint(readOnly=[], readWrite=[]),
        instructions=1_000_000, readBytes=5000, writeBytes=2000)
    non_ref, ref = compute_transaction_resource_fee(res, 500, 1000, cfg)
    assert non_ref > 0 and ref > 0
    # more instructions → more fee
    res2 = cx.SorobanResources(
        footprint=cx.LedgerFootprint(readOnly=[], readWrite=[]),
        instructions=10_000_000, readBytes=5000, writeBytes=2000)
    non_ref2, _ = compute_transaction_resource_fee(res2, 500, 1000, cfg)
    assert non_ref2 > non_ref
    # write fee grows with bucket list size
    low = compute_write_fee_per_1kb(0, cfg.ledger_cost)
    high = compute_write_fee_per_1kb(10 * 1024**3, cfg.ledger_cost)
    assert high > low


def test_soroban_config_upgrades(tmp_path):
    """LEDGER_UPGRADE_MAX_SOROBAN_TX_SET_SIZE and LEDGER_UPGRADE_CONFIG
    applied through a close (reference: Upgrades.cpp:301-362 +
    ConfigUpgradeSetFrame:1273-1400)."""
    import base64
    from stellar_core_tpu.crypto.sha import sha256
    from stellar_core_tpu.herder.upgrades import ConfigUpgradeSetFrame
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.soroban.host import ttl_key_for
    from stellar_core_tpu.soroban.network_config import SorobanNetworkConfig
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr.contract import (
        ConfigSettingEntry, ConfigSettingID, ConfigUpgradeSet,
        ConfigUpgradeSetKey, ContractDataDurability, ContractDataEntry,
        SCAddress, SCAddressType, SCVal, SCValType, TTLEntry)
    from stellar_core_tpu.xdr.ledger_entries import (LedgerEntry,
                                                     LedgerEntryType,
                                                     _LedgerEntryData,
                                                     _LedgerEntryExt)
    from stellar_core_tpu.xdr.types import ExtensionPoint

    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        # 1. max-soroban-tx-set-size via the admin API
        r = app.command_handler.handle("upgrades", {
            "mode": "set", "upgradetime": "0",
            "maxsorobantxsetsize": "55"})
        assert r["status"] == "ok"
        app.manual_close()
        with LedgerTxn(app.ledger_manager.root) as ltx:
            cfg = SorobanNetworkConfig(ltx)
            lanes = cfg._get(
                ConfigSettingID.CONFIG_SETTING_CONTRACT_EXECUTION_LANES)
            assert lanes.ledgerMaxTxCount == 55

        # 2. CONFIG upgrade: publish an upgrade set as TEMPORARY
        # contract data, then vote its key
        new_entry = ConfigSettingEntry(
            ConfigSettingID.CONFIG_SETTING_CONTRACT_MAX_SIZE_BYTES,
            131072)
        upgrade_set = ConfigUpgradeSet(updatedEntry=[new_entry])
        content_hash = sha256(upgrade_set.to_bytes())
        key = ConfigUpgradeSetKey(contractID=b"\x42" * 32,
                                  contentHash=content_hash)
        lk = ConfigUpgradeSetFrame.ledger_key(key)
        with LedgerTxn(app.ledger_manager.root) as ltx:
            cd = ContractDataEntry(
                ext=ExtensionPoint(0),
                contract=SCAddress(
                    SCAddressType.SC_ADDRESS_TYPE_CONTRACT, b"\x42" * 32),
                key=SCVal(SCValType.SCV_BYTES, bytes(content_hash)),
                durability=ContractDataDurability.TEMPORARY,
                val=SCVal(SCValType.SCV_BYTES, upgrade_set.to_bytes()))
            ltx.create(LedgerEntry(
                lastModifiedLedgerSeq=0,
                data=_LedgerEntryData(LedgerEntryType.CONTRACT_DATA, cd),
                ext=_LedgerEntryExt(0)))
            ttl = TTLEntry(keyHash=sha256(lk.to_bytes()),
                           liveUntilLedgerSeq=10_000)
            ltx.create(LedgerEntry(
                lastModifiedLedgerSeq=0,
                data=_LedgerEntryData(LedgerEntryType.TTL, ttl),
                ext=_LedgerEntryExt(0)))
            ltx.commit()

        r = app.command_handler.handle("upgrades", {
            "mode": "set", "upgradetime": "0",
            "configupgradesetkey":
                base64.b64encode(key.to_bytes()).decode()})
        assert r["status"] == "ok"
        app.manual_close()
        with LedgerTxn(app.ledger_manager.root) as ltx:
            cfg = SorobanNetworkConfig(ltx)
            max_size = cfg._get(
                ConfigSettingID.CONFIG_SETTING_CONTRACT_MAX_SIZE_BYTES)
            assert max_size == 131072

        # 3. a key pointing at missing data produces no vote (no crash)
        bogus = ConfigUpgradeSetKey(contractID=b"\x43" * 32,
                                    contentHash=b"\x44" * 32)
        r = app.command_handler.handle("upgrades", {
            "mode": "set", "upgradetime": "0",
            "configupgradesetkey":
                base64.b64encode(bogus.to_bytes()).decode()})
        assert r["status"] == "ok"
        lcl = app.ledger_manager.get_last_closed_ledger_num()
        app.manual_close()
        assert app.ledger_manager.get_last_closed_ledger_num() == lcl + 1
    finally:
        app.shutdown()


def test_config_upgrade_validation_rejects_bad_sets():
    """Non-upgradeable ids and zero limits are rejected at load;
    unloadable keys are rejected at ballot validation with an ltx
    (reference: ConfigUpgradeSetFrame::isValid + isValidForApply)."""
    from stellar_core_tpu.herder.upgrades import (ConfigUpgradeSetFrame,
                                                  Upgrades,
                                                  _is_valid_config_entry)
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr.contract import (
        ConfigSettingContractExecutionLanesV0, ConfigSettingEntry,
        ConfigSettingID, ConfigUpgradeSetKey)
    from stellar_core_tpu.xdr.ledger import LedgerUpgrade, LedgerUpgradeType

    # internal bookkeeping setting: not upgradeable
    from stellar_core_tpu.xdr.contract import StateArchivalSettings
    bad = ConfigSettingEntry(
        ConfigSettingID.CONFIG_SETTING_CONTRACT_EXECUTION_LANES,
        ConfigSettingContractExecutionLanesV0(ledgerMaxTxCount=0))
    assert not _is_valid_config_entry(bad)
    ok = ConfigSettingEntry(
        ConfigSettingID.CONFIG_SETTING_CONTRACT_EXECUTION_LANES,
        ConfigSettingContractExecutionLanesV0(ledgerMaxTxCount=10))
    assert _is_valid_config_entry(ok)

    # ballot-stage: a CONFIG upgrade whose key loads nothing is invalid
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             get_test_config())
    app.start()
    try:
        up = LedgerUpgrade(
            LedgerUpgradeType.LEDGER_UPGRADE_CONFIG,
            ConfigUpgradeSetKey(contractID=b"\x01" * 32,
                                contentHash=b"\x02" * 32))
        lcl = app.ledger_manager.get_last_closed_ledger_header()
        with LedgerTxn(app.ledger_manager.root) as ltx:
            assert not app.herder.upgrades.is_valid(
                up, lcl, nomination=False, ltx=ltx)
        # without an ltx (structural check only) it still passes, as in
        # the reference's isValid(..., nomination=false)
        assert app.herder.upgrades.is_valid(up, lcl, nomination=False)
    finally:
        app.shutdown()


def test_auth_tuples_collected_for_batch(app):
    """Address-credential auth signatures are collected as batch-verify
    tuples with the exact payload the host checks (BASELINE.md config
    #4: auth-entry batches)."""
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.soroban.host import soroban_auth_payload
    from stellar_core_tpu.tx.signature_checker import (
        PrevalidatedVerifier, collect_signature_tuples)

    master, cid = deploy(app)
    signer = SecretKey.from_seed(sha256(b"auth-signer"))
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                        PublicKey.ed25519(signer.public_key().raw))
    addr_val = cx.SCVal(cx.SCValType.SCV_ADDRESS, addr)
    root_inv = cx.SorobanAuthorizedInvocation(
        function=cx.SorobanAuthorizedFunction(
            cx.SorobanAuthorizedFunctionType
            .SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN,
            cx.InvokeContractArgs(
                contractAddress=cx.SCAddress(
                    cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid),
                functionName=b"auth_bump", args=[addr_val])),
        subInvocations=[])
    nonce, expiration = 7, 10_000
    payload = soroban_auth_payload(app.config.network_id(), nonce,
                                   expiration, root_inv)
    sig = signer.sign(payload)
    sig_val = cx.SCVal(cx.SCValType.SCV_VEC, [cx.SCVal(
        cx.SCValType.SCV_MAP, [
            cx.SCMapEntry(key=cx.SCVal(cx.SCValType.SCV_SYMBOL,
                                       b"public_key"),
                          val=cx.SCVal(cx.SCValType.SCV_BYTES,
                                       signer.public_key().raw)),
            cx.SCMapEntry(key=cx.SCVal(cx.SCValType.SCV_SYMBOL,
                                       b"signature"),
                          val=cx.SCVal(cx.SCValType.SCV_BYTES, sig)),
        ])])
    body = invoke_op(cid, "auth_bump", [addr_val])
    body.value.auth = [cx.SorobanAuthorizationEntry(
        credentials=cx.SorobanCredentials(
            cx.SorobanCredentialsType.SOROBAN_CREDENTIALS_ADDRESS,
            cx.SorobanAddressCredentials(
                address=addr, nonce=nonce,
                signatureExpirationLedger=expiration,
                signature=sig_val)),
        rootInvocation=root_inv)]
    frame = soroban_tx(app, master, body, *invoke_footprints(cid))

    tuples = collect_signature_tuples([frame], app.config.network_id())
    # envelope signature + the auth-entry signature
    auth_tuples = [t for t in tuples if t[2] == payload]
    assert len(auth_tuples) == 1
    pub, s, m = auth_tuples[0]
    assert pub == signer.public_key().raw and s == sig
    # the batch result is exactly what the host's verify call consumes
    from stellar_core_tpu.crypto import ed25519_ref as ref
    pv = PrevalidatedVerifier()
    pv.add_results(tuples, [ref.verify(p, sg, ms) for p, sg, ms in tuples])
    assert pv(pub, s, m) is True
    assert pv.misses == 0
    # ... and through apply, not only by hand: a close that is given the
    # table hands it to the Soroban host (tests/test_soroban_auth.py
    # holds the seam at a checkpoint's size)
    lm = app.ledger_manager
    close = lm.close_ledger
    lm.close_ledger = lambda lcd, verify=None: close(lcd, verify=pv)
    asked = pv.hits
    pair = submit_and_close(app, frame)
    from stellar_core_tpu.xdr.results import TransactionResultCode
    assert pair.result.result.disc == TransactionResultCode.txSUCCESS
    assert pv.misses == 0 and pv.hits > asked + 1
    seen = app.metrics.to_json()
    assert seen["soroban.auth.verify.prevalidated"]["count"] == 1
    assert seen["soroban.auth.verify.fallback"]["count"] == 0


def test_malformed_auth_signature_never_crashes(app):
    """A void-typed signature map (valid XDR, hostile content) must not
    crash collection or the host — it yields no tuples and the host
    raises a clean auth error (remote-DoS guard)."""
    from stellar_core_tpu.tx.signature_checker import (
        collect_signature_tuples)

    master, cid = deploy(app)
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                        master.account_id)
    addr_val = cx.SCVal(cx.SCValType.SCV_ADDRESS, addr)
    bad_sig = cx.SCVal(cx.SCValType.SCV_VEC, [cx.SCVal(
        cx.SCValType.SCV_MAP, [
            cx.SCMapEntry(key=cx.SCVal(cx.SCValType.SCV_SYMBOL,
                                       b"public_key"),
                          val=cx.SCVal(cx.SCValType.SCV_VOID)),
            cx.SCMapEntry(key=cx.SCVal(cx.SCValType.SCV_SYMBOL,
                                       b"signature"),
                          val=cx.SCVal(cx.SCValType.SCV_VOID)),
        ])])
    body = invoke_op(cid, "auth_bump", [addr_val])
    body.value.auth = [cx.SorobanAuthorizationEntry(
        credentials=cx.SorobanCredentials(
            cx.SorobanCredentialsType.SOROBAN_CREDENTIALS_ADDRESS,
            cx.SorobanAddressCredentials(
                address=addr, nonce=1, signatureExpirationLedger=10_000,
                signature=bad_sig)),
        rootInvocation=cx.SorobanAuthorizedInvocation(
            function=cx.SorobanAuthorizedFunction(
                cx.SorobanAuthorizedFunctionType
                .SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN,
                cx.InvokeContractArgs(
                    contractAddress=cx.SCAddress(
                        cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid),
                    functionName=b"auth_bump", args=[addr_val])),
            subInvocations=[]))]
    frame = soroban_tx(app, master, body, [], [])
    # collection is total: no tuples, no crash
    tuples = collect_signature_tuples([frame], app.config.network_id())
    assert all(len(t[0]) == 32 for t in tuples)
    # the apply path fails with a clean auth error, not a TypeError
    r = m1.submit(app, frame)
    assert r["status"] == "PENDING", r
    app.manual_close()
    from stellar_core_tpu.xdr.results import TransactionResultPair
    row = app.database.query_one(
        "SELECT txresult FROM txhistory WHERE txid=?", (frame.full_hash(),))
    pair = TransactionResultPair.from_bytes(bytes(row[0]))
    assert pair.result.result.disc.name == "txFAILED"


def test_diagnostic_events_in_v3_meta():
    """ENABLE_SOROBAN_DIAGNOSTIC_EVENTS surfaces the host's log sink as
    DIAGNOSTIC events in sorobanMeta (reference: Config.h:571; off by
    default — off-consensus, never hashed)."""
    global COUNTER_CODE
    saved_code = COUNTER_CODE
    COUNTER_CODE = scvm.make_code(NOISY_FUNCTIONS)
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr.ledger import TransactionMeta
    cfg = get_test_config()
    cfg.ENABLE_SOROBAN_DIAGNOSTIC_EVENTS = True
    try:
        _run_diagnostic_scenario(cfg)
    finally:
        COUNTER_CODE = saved_code


def _run_diagnostic_scenario(cfg):
    from stellar_core_tpu.main import Application
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr.ledger import TransactionMeta
    with Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg) as a:
        a.start()
        master, cid = deploy(a)
        ro, rw = invoke_footprints(cid)
        res = submit_and_close(a, soroban_tx(
            a, master, invoke_op(cid, "increment"), ro, rw))
        assert res.result.result.disc.name == "txSUCCESS", res
        # a contract that logs: the diagnostic lands in sorobanMeta
        res = submit_and_close(a, soroban_tx(
            a, master, invoke_op(cid, "noisy"), ro, rw))
        assert res.result.result.disc.name == "txSUCCESS", res
        row = a.database.query_one(
            "SELECT txmeta FROM txhistory WHERE txid=?",
            (bytes(res.transactionHash),))
        meta = TransactionMeta.from_bytes(bytes(row[0]))
        assert meta.disc == 3
        des = meta.value.sorobanMeta.diagnosticEvents
        assert len(des) == 1
        assert des[0].inSuccessfulContractCall
        body = des[0].event.body.value
        assert bytes(body.topics[0].value) == b"log"
        assert bytes(body.topics[1].value) == b"hello-diag"
        # a FAILED invocation still surfaces its diagnostics, marked
        # inSuccessfulContractCall=false (the reference's primary use)
        res = submit_and_close(a, soroban_tx(
            a, master, invoke_op(cid, "noisy_boom"), ro, rw))
        assert res.result.result.disc.name == "txFAILED"
        row = a.database.query_one(
            "SELECT txmeta FROM txhistory WHERE txid=?",
            (bytes(res.transactionHash),))
        meta = TransactionMeta.from_bytes(bytes(row[0]))
        assert meta.disc == 3
        des = meta.value.sorobanMeta.diagnosticEvents
        assert len(des) == 1
        assert not des[0].inSuccessfulContractCall
        assert meta.value.sorobanMeta.events == []
