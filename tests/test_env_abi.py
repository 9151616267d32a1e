"""Real soroban-env ABI tests.

Three tiers:
 1. Val-encoding unit tests against the facts recovered from the
    reference's SDK-built binaries (tags in the low 4 bits, U32 tag 3,
    symbol tag 9, `return 5` void idiom).
 2. The in-repo hand-assembled env-ABI counter contract
    (soroban/env_contract.py) through the SAME upload→create→invoke
    scenario matrix the scvm/wasm twins run in tests/test_soroban.py —
    storage, traps, auth, events, budget — plus bulk-memory coverage.
 3. Acceptance: the reference's ACTUAL vendored SDK-built wasm binaries
    (read at test time from /root/reference, never copied into the
    repo) deploy and execute on this VM — the "run a real-ecosystem
    contract" capability. Loud skip when the reference tree is absent.
"""

import os

import pytest

from stellar_core_tpu.crypto.sha import sha256
from stellar_core_tpu.soroban import env_abi
from stellar_core_tpu.soroban.env_contract import (COPY_HASH_PREIMAGE,
                                                   build_env_counter)
from stellar_core_tpu.xdr import contract as cx

import test_soroban as ts

REF_TESTDATA = "/root/reference/src/testdata"


# ---------------------------------------------------------------- tier 1 --
def test_val_encoding_ground_truth():
    # the observed constants: tag 3 = I32 (the reference invokes
    # add_i32 with makeI32; the contract overflow-checks SIGNED add)
    assert env_abi.TAG_I32 == 3 and env_abi.TAG_SYMBOL == 9
    assert env_abi.VAL_VOID == 5            # both reference contracts
    v = (12345 << 4) | 3
    assert env_abi.EnvCtx(None, None, [None]).from_val(v) == \
        cx.SCVal(cx.SCValType.SCV_I32, 12345)
    neg = ((-7 & 0xFFFFFFFF) << 4) | 3
    assert env_abi.EnvCtx(None, None, [None]).from_val(neg) == \
        cx.SCVal(cx.SCValType.SCV_I32, -7)


def test_symbol_roundtrip():
    for name in (b"count", b"a", b"_", b"Z9z_", b"abcdefghij"):
        val = env_abi.symbol_to_val(name)
        assert val is not None and val & 0xF == env_abi.TAG_SYMBOL
        assert env_abi.val_to_symbol(val) == name
    assert env_abi.symbol_to_val(b"elevenchars") is None      # too long
    assert env_abi.symbol_to_val(b"sp ace") is None           # bad char


def test_scval_val_bridge_roundtrip():
    ectx = env_abi.EnvCtx(None, None, [cx.SCVal(cx.SCValType.SCV_VOID)])
    cases = [
        cx.SCVal(cx.SCValType.SCV_VOID),
        cx.SCVal(cx.SCValType.SCV_BOOL, True),
        cx.SCVal(cx.SCValType.SCV_BOOL, False),
        cx.SCVal(cx.SCValType.SCV_U32, 0),
        cx.SCVal(cx.SCValType.SCV_U32, 0xFFFFFFFF),
        cx.SCVal(cx.SCValType.SCV_I32, -1),
        cx.SCVal(cx.SCValType.SCV_I32, 2**31 - 1),
        cx.SCVal(cx.SCValType.SCV_SYMBOL, b"hello"),
        cx.SCVal(cx.SCValType.SCV_U64, 2**40),      # via object handle
        cx.SCVal(cx.SCValType.SCV_BYTES, b"\x00\x01"),
    ]
    for v in cases:
        assert ectx.from_val(ectx.to_val(v)) == v


def test_env_abi_module_detection():
    from stellar_core_tpu.soroban.env_abi import is_env_abi_module
    from stellar_core_tpu.soroban.wasm import decode
    m = decode.decode_module(build_env_counter())
    assert is_env_abi_module(m)
    # the scvm_wasm twin uses the bespoke long-name module
    m2 = decode.decode_module(ts.CODE_BUILDS["wasm"])
    assert not is_env_abi_module(m2)


# ---------------------------------------------------------------- tier 2 --
@pytest.fixture
def app():
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    old = ts.COUNTER_CODE
    ts.COUNTER_CODE = build_env_counter()
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    cfg = get_test_config()
    try:
        with Application.create(clock, cfg) as a:
            a.start()
            yield a
    finally:
        ts.COUNTER_CODE = old


def test_env_counter_full_matrix(app):
    """upload → create → invoke ×2 → trap — mirroring the twins."""
    master, cid = ts.deploy(app)
    ro, rw = ts.invoke_footprints(cid)

    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "increment"), ro, rw))
    assert res.result.result.disc.name == "txSUCCESS", res
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "increment"), ro, rw))
    assert res.result.result.disc.name == "txSUCCESS", res

    # stored count is a real SCVal in the contract-data entry
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    with LedgerTxn(app.ledger_manager.root) as ltx:
        le = ltx.load_without_record(ts.counter_key(cid))
        assert le is not None
        assert le.data.value.val == cx.SCVal(cx.SCValType.SCV_U32, 2)

    # get_count returns it
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "get_count"), ro + rw, []))
    assert res.result.result.disc.name == "txSUCCESS", res

    # boom traps the tx (fail_with_error path)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "boom"), ro, rw))
    assert res.result.result.disc.name == "txFAILED", res


def test_env_counter_budget_exhaustion(app):
    master, cid = ts.deploy(app)
    ro, rw = ts.invoke_footprints(cid)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "increment"), ro, rw,
        instructions=10))
    assert res.result.result.disc.name == "txFAILED", res


def test_env_counter_auth_and_event(app):
    master, cid = ts.deploy(app)
    ro, rw = ts.invoke_footprints(cid)
    addr_val = cx.SCVal(
        cx.SCValType.SCV_ADDRESS,
        cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                     master.account_id))
    body = ts.invoke_op(cid, "auth_bump", [addr_val])
    op = body.value
    op.auth = [cx.SorobanAuthorizationEntry(
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
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, body, ro, rw))
    assert res.result.result.disc.name == "txSUCCESS", res


def test_env_counter_bulk_memory(app):
    """memory.init / fill / copy feed bytes_new + sha256; data.drop
    then memory.init traps."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.xdr.ledger_entries import LedgerKey

    master, cid = ts.deploy(app)
    ro, rw = ts.invoke_footprints(cid)
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    hash_key = LedgerKey.contract_data(
        addr, cx.SCVal(cx.SCValType.SCV_SYMBOL, b"hash"),
        cx.ContractDataDurability.PERSISTENT)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "copy_hash"), ro,
        rw + [hash_key]))
    assert res.result.result.disc.name == "txSUCCESS", res
    with LedgerTxn(app.ledger_manager.root) as ltx:
        le = ltx.load_without_record(hash_key)
        assert le is not None
        assert le.data.value.val == cx.SCVal(
            cx.SCValType.SCV_BYTES, sha256(COPY_HASH_PREIMAGE))

    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "drop_then_init"), ro, rw))
    assert res.result.result.disc.name == "txFAILED", res


# ---------------------------------------------------------------- tier 3 --
needs_reference = pytest.mark.skipif(
    not os.path.isdir(REF_TESTDATA),
    reason="SKIPPED LOUDLY: /root/reference testdata not present — the "
           "SDK-built wasm acceptance tier needs the reference snapshot")


@needs_reference
def test_reference_sdk_contract_add_i32_direct():
    """The reference's actual SDK-built example_add_i32.wasm executes
    on this VM (it imports nothing, so the raw Instance + Val encoding
    suffices): add(U32Val 5, U32Val 7) == U32Val 12, and u32 overflow
    hits the contract's own `unreachable`."""
    from stellar_core_tpu.soroban.wasm import (Instance, WasmTrap,
                                               decode_module,
                                               validate_module)
    with open(os.path.join(REF_TESTDATA, "example_add_i32.wasm"),
              "rb") as f:
        code = f.read()
    m = decode_module(code)
    validate_module(m)
    assert env_abi.is_env_abi_module(m)
    inst = Instance(m, imports={})
    i32 = lambda n: ((n & 0xFFFFFFFF) << 4) | env_abi.TAG_I32  # noqa: E731
    out = inst.invoke("add", [i32(5), i32(7)])
    assert out == [i32(12)]
    with pytest.raises(WasmTrap):                  # INT32_MAX + 1
        Instance(m, imports={}).invoke(
            "add", [i32(2**31 - 1), i32(1)])
    # non-I32 tag rejected by the contract's own check
    with pytest.raises(WasmTrap):
        Instance(m, imports={}).invoke("add", [env_abi.VAL_VOID, i32(1)])


@needs_reference
def test_reference_sdk_contract_add_i32_deployed(app):
    """Same binary through the full upload→create→invoke tx flow."""
    with open(os.path.join(REF_TESTDATA, "example_add_i32.wasm"),
              "rb") as f:
        ts.COUNTER_CODE = f.read()
    master, cid = ts.deploy(app)
    ro, _rw = ts.invoke_footprints(cid)
    args = [cx.SCVal(cx.SCValType.SCV_I32, 5),
            cx.SCVal(cx.SCValType.SCV_I32, 7)]
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "add", args), ro, []))
    assert res.result.result.disc.name == "txSUCCESS", res

    # the reference's "failed invocation with diagnostics" scenario:
    # INT32_MAX + 7 overflows and the invocation fails
    args = [cx.SCVal(cx.SCValType.SCV_I32, 2**31 - 1),
            cx.SCVal(cx.SCValType.SCV_I32, 7)]
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "add", args), ro, []))
    assert res.result.result.disc.name == "txFAILED", res


@needs_reference
def test_reference_sdk_contract_contract_data(app):
    """example_contract_data.wasm: put/del through ("l","_")/("l","2")
    — the imports that pinned the ledger-module function order."""
    with open(os.path.join(REF_TESTDATA, "example_contract_data.wasm"),
              "rb") as f:
        ts.COUNTER_CODE = f.read()
    master, cid = ts.deploy(app)
    ro, _rw = ts.invoke_footprints(cid)
    addr = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, cid)
    key = cx.SCVal(cx.SCValType.SCV_SYMBOL, b"key")
    val = cx.SCVal(cx.SCValType.SCV_SYMBOL, b"val")
    from stellar_core_tpu.xdr.ledger_entries import LedgerKey
    dk = LedgerKey.contract_data(
        addr, key, cx.ContractDataDurability.PERSISTENT)

    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "put", [key, val]), ro, [dk]))
    assert res.result.result.disc.name == "txSUCCESS", res
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    with LedgerTxn(app.ledger_manager.root) as ltx:
        le = ltx.load_without_record(dk)
        assert le is not None and le.data.value.val == val

    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "del", [key]), ro, [dk]))
    assert res.result.result.disc.name == "txSUCCESS", res
    with LedgerTxn(app.ledger_manager.root) as ltx:
        assert ltx.load_without_record(dk) is None

    # non-symbol key: the contract's own tag check hits `unreachable`
    bad = cx.SCVal(cx.SCValType.SCV_U32, 1)
    res = ts.submit_and_close(app, ts.soroban_tx(
        app, master, ts.invoke_op(cid, "put", [bad, val]), ro, [dk]))
    assert res.result.result.disc.name == "txFAILED", res


# ------------------------------------------------- extended env surface ----
def _table_ctx(app, footprint_keys_rw=()):
    """A live SorobanHost + EnvCtx + env table for table-level tests."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.soroban.host import Budget, SorobanHost
    from stellar_core_tpu.soroban.network_config import SorobanNetworkConfig
    from stellar_core_tpu.xdr.contract import LedgerFootprint
    from stellar_core_tpu.xdr.types import PublicKey

    ltx = LedgerTxn(app.ledger_manager.root)
    header = app.ledger_manager.get_last_closed_ledger_header()
    config = SorobanNetworkConfig(ltx)
    fp = LedgerFootprint(readOnly=[], readWrite=list(footprint_keys_rw))
    host = SorobanHost(ltx, header, config, fp, Budget(10**9),
                       app.config.network_id(),
                       PublicKey.ed25519(b"\x01" * 32))
    contract = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                            b"\x07" * 32)
    ectx = env_abi.EnvCtx(host, contract, [cx.SCVal(cx.SCValType.SCV_VOID)])
    table = env_abi.env_host_table(ectx, lambda f: f)
    fns = {}
    for (mod, name), hf in table.items():
        fns[(mod, name)] = hf.fn
    return ltx, host, ectx, fns


class _FakeInst:
    def __init__(self, size=65536):
        self.memory = bytearray(size)


def test_map_module_semantics(app):
    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        sym = env_abi.symbol_to_val
        m = fns[("m", "_")](inst)
        m = fns[("m", "0")](inst, m, sym(b"zz"), u32(26))
        m = fns[("m", "0")](inst, m, sym(b"aa"), u32(1))
        m = fns[("m", "0")](inst, m, sym(b"mm"), u32(13))
        # sorted iteration order regardless of insertion order
        keys = ectx.get_obj(fns[("m", "5")](inst, m))
        assert [bytes(k.value) for k in keys.value] == [b"aa", b"mm", b"zz"]
        vals = ectx.get_obj(fns[("m", "6")](inst, m))
        assert [v.value for v in vals.value] == [1, 13, 26]
        # replace keeps length; get returns the new value
        m = fns[("m", "0")](inst, m, sym(b"mm"), u32(99))
        assert fns[("m", "4")](inst, m) == u32(3)
        assert fns[("m", "1")](inst, m, sym(b"mm")) == u32(99)
        # has / del / missing-key error
        assert fns[("m", "2")](inst, m, sym(b"aa")) == env_abi.VAL_TRUE
        m = fns[("m", "3")](inst, m, sym(b"aa"))
        assert fns[("m", "2")](inst, m, sym(b"aa")) == env_abi.VAL_FALSE
        from stellar_core_tpu.soroban.host import HostError
        with pytest.raises(HostError):
            fns[("m", "1")](inst, m, sym(b"aa"))
        with pytest.raises(HostError):
            fns[("m", "3")](inst, m, sym(b"aa"))
    finally:
        ltx.rollback()


def test_vec_and_bytes_extensions(app):
    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        v = fns[("v", "_")](inst)
        for n in (10, 20, 30):
            v = fns[("v", "0")](inst, v, u32(n))
        assert fns[("v", "3")](inst, v) == u32(10)        # front
        assert fns[("v", "4")](inst, v) == u32(30)        # back
        v2 = fns[("v", "5")](inst, v, u32(1), u32(15))    # insert
        assert [x.value for x in ectx.get_obj(v2).value] == [10, 15, 20, 30]
        v3 = fns[("v", "6")](inst, v2, u32(0))            # del
        assert [x.value for x in ectx.get_obj(v3).value] == [15, 20, 30]
        v4 = fns[("v", "7")](inst, v3, v)                 # append
        assert len(ectx.get_obj(v4).value) == 6
        v5 = fns[("v", "8")](inst, v4, u32(1), u32(4))    # slice
        assert [x.value for x in ectx.get_obj(v5).value] == [20, 30, 10]

        b0 = fns[("b", "2")](inst)                        # bytes_new
        assert bytes(ectx.get_obj(b0).value) == b""
        inst.memory[0:4] = b"\xde\xad\xbe\xef"
        b1 = fns[("b", "_")](inst, u32(0), u32(4))
        b2 = fns[("b", "3")](inst, b1, b1)                # append
        assert bytes(ectx.get_obj(b2).value) == b"\xde\xad\xbe\xef" * 2
        b3 = fns[("b", "4")](inst, b2, u32(2), u32(6))    # slice
        assert bytes(ectx.get_obj(b3).value) == b"\xbe\xef\xde\xad"
        b4 = fns[("b", "5")](inst, b3, u32(0x7F))         # push
        assert fns[("b", "6")](inst, b4, u32(4)) == u32(0x7F)   # get
        b5 = fns[("b", "7")](inst, b4, u32(0), u32(1))    # put
        assert bytes(ectx.get_obj(b5).value)[0] == 1
        inst.memory[100:103] = b"xyz"
        b6 = fns[("b", "8")](inst, b5, u32(1), u32(100), u32(3))
        assert bytes(ectx.get_obj(b6).value)[1:4] == b"xyz"
    finally:
        ltx.rollback()


def test_i128_string_timepoint_objects(app):
    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        h = fns[("i", "3")](inst, (1 << 64) - 1, 7)   # hi=-1 (signed), lo=7
        assert fns[("i", "4")](inst, h) == 7
        assert fns[("i", "5")](inst, h) == (1 << 64) - 1
        v = ectx.get_obj(h)
        assert v.disc == cx.SCValType.SCV_I128 and v.value.hi == -1
        hu = fns[("i", "6")](inst, 2**63, 3)
        vu = ectx.get_obj(hu)
        assert vu.disc == cx.SCValType.SCV_U128 and vu.value.hi == 2**63
        hi64 = fns[("i", "1")](inst, (1 << 64) - 5)   # obj_from_i64 → -5
        assert ectx.get_obj(hi64).value == -5
        assert fns[("i", "2")](inst, hi64) == (1 << 64) - 5
        tp = fns[("i", "9")](inst, 1234567)
        assert ectx.get_obj(tp).disc == cx.SCValType.SCV_TIMEPOINT
        assert fns[("i", "A")](inst, tp) == 1234567

        inst.memory[10:15] = b"hello"
        s = fns[("s", "_")](inst, u32(10), u32(5))
        assert fns[("s", "0")](inst, s) == u32(5)
        fns[("s", "1")](inst, s, u32(1), u32(50), u32(4))
        assert bytes(inst.memory[50:54]) == b"ello"
    finally:
        ltx.rollback()


def test_prng_deterministic_and_log(app):
    from stellar_core_tpu.soroban.host import HostError

    def run_stream():
        """Draws + a shuffle from a FRESH host at the same ledger —
        two invocations must see the identical deterministic stream."""
        ltx, host, ectx, fns = _table_ctx(app)
        try:
            inst = _FakeInst()
            u32 = lambda n: (n << 4) | env_abi.TAG_U32
            draws = [ectx.get_obj(fns[("p", "0")](inst, 10, 20)).value
                     for _ in range(8)]
            v = fns[("v", "_")](inst)
            for n in range(10):
                v = fns[("v", "0")](inst, v, u32(n))
            shuffled = [x.value for x in ectx.get_obj(
                fns[("p", "1")](inst, v)).value]
            return draws, shuffled
        finally:
            ltx.rollback()

    a, s1 = run_stream()
    b, s2 = run_stream()
    assert a == b and all(10 <= x <= 20 for x in a)
    assert sorted(s1) == list(range(10)) and s1 == s2

    # ... but two invocation FRAMES on the SAME host (a repeated
    # cross-contract call within one tx) draw different streams
    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        contract = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                                b"\x07" * 32)
        ectx2 = env_abi.EnvCtx(host, contract,
                               [cx.SCVal(cx.SCValType.SCV_VOID)])
        fns2 = {k: hf.fn for k, hf in
                env_abi.env_host_table(ectx2, lambda f: f).items()}
        d1 = [ectx.get_obj(fns[("p", "0")](inst, 0, 2**32)).value
              for _ in range(4)]
        d2 = [ectx2.get_obj(fns2[("p", "0")](inst, 0, 2**32)).value
              for _ in range(4)]
        assert d1 != d2
    finally:
        ltx.rollback()

    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        with pytest.raises(HostError):
            fns[("p", "0")](inst, 21, 20)                 # empty range
        # log_from_linear_memory lands in host.diagnostics, off-state
        inst.memory[0:5] = b"debug"
        import struct as _s
        inst.memory[8:16] = _s.pack("<Q", u32(77))
        fns[("x", "6")](inst, u32(0), u32(5), u32(8), u32(1))
        assert host.diagnostics == [(b"debug",
                                     [cx.SCVal(cx.SCValType.SCV_U32, 77)])]
    finally:
        ltx.rollback()


def test_ledger_context_and_ttl(app):
    from stellar_core_tpu.soroban.host import HostError, ttl_key_for
    from stellar_core_tpu.xdr.ledger_entries import LedgerKey
    # storage fns need the key in the footprint: build it first
    contract = cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                            b"\x07" * 32)
    sym_k = cx.SCVal(cx.SCValType.SCV_SYMBOL, b"k")
    lk = LedgerKey.contract_data(contract, sym_k,
                                 cx.ContractDataDurability.PERSISTENT)
    ltx, host, ectx, fns = _table_ctx(app, footprint_keys_rw=[lk])
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        assert ectx.get_obj(fns[("x", "4")](inst)).disc == \
            cx.SCValType.SCV_TIMEPOINT
        nid = ectx.get_obj(fns[("x", "5")](inst))
        assert bytes(nid.value) == app.config.network_id()

        kval = env_abi.symbol_to_val(b"k")
        fns[("l", "_")](inst, kval, u32(5))               # put
        ttl0 = ltx.load(ttl_key_for(lk)).data.value.liveUntilLedgerSeq
        # far-future threshold forces the extension; verify liveUntil
        fns[("l", "3")](inst, kval, u32(10**6), u32(10**6))
        ttl1 = ltx.load(ttl_key_for(lk)).data.value.liveUntilLedgerSeq
        assert ttl1 > ttl0
        assert host.rent_changes[-1]["new_live_until"] == ttl1
        # threshold below remaining TTL → no-op
        fns[("l", "3")](inst, kval, u32(1), u32(10**6))
        assert ltx.load(ttl_key_for(lk)).data.value.liveUntilLedgerSeq \
            == ttl1
        with pytest.raises(HostError):                    # bad args
            fns[("l", "3")](inst, kval, u32(10), u32(5))
    finally:
        ltx.rollback()


def test_verify_sig_ed25519_host_fn(app):
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.soroban.host import HostError
    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        sk = SecretKey.pseudo_random_for_testing(99)
        msg = b"soroban-env verify"
        sig = sk.sign(msg)
        mk = lambda b: ectx.put_obj(cx.SCVal(cx.SCValType.SCV_BYTES, b))
        assert fns[("c", "0")](inst, mk(sk.public_key().raw), mk(msg),
                               mk(sig)) == env_abi.VAL_VOID
        bad = sig[:-1] + bytes([sig[-1] ^ 1])
        with pytest.raises(HostError):
            fns[("c", "0")](inst, mk(sk.public_key().raw), mk(msg),
                            mk(bad))
        with pytest.raises(HostError):                    # length check
            fns[("c", "0")](inst, mk(b"\x00" * 31), mk(msg), mk(sig))
    finally:
        ltx.rollback()


def test_env_toolkit_contract_end_to_end(app):
    """The second hand-assembled env-ABI contract: map/i128/string/
    verify_sig through real wasm, upload → create → invoke."""
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.soroban.env_contract import build_env_toolkit
    import test_soroban as ts_mod

    old = ts_mod.COUNTER_CODE
    ts_mod.COUNTER_CODE = build_env_toolkit()
    try:
        master, cid = ts_mod.deploy(app)
        ro, rw = ts_mod.invoke_footprints(cid)
        for fn, want in (("map_demo", cx.SCVal(cx.SCValType.SCV_U32, 1)),
                         ("i128_demo", cx.SCVal(cx.SCValType.SCV_U32, 42)),
                         ("str_demo", cx.SCVal(cx.SCValType.SCV_U32, 7))):
            res = ts_mod.submit_and_close(app, ts_mod.soroban_tx(
                app, master, ts_mod.invoke_op(cid, fn), ro, rw))
            assert res.result.result.disc.name == "txSUCCESS", (fn, res)

        sk = SecretKey.pseudo_random_for_testing(7)
        msg = b"toolkit message"
        sig = sk.sign(msg)
        mkb = lambda b: cx.SCVal(cx.SCValType.SCV_BYTES, b)
        res = ts_mod.submit_and_close(app, ts_mod.soroban_tx(
            app, master, ts_mod.invoke_op(
                cid, "sig_demo",
                [mkb(sk.public_key().raw), mkb(msg), mkb(sig)]), ro, rw))
        assert res.result.result.disc.name == "txSUCCESS", res
        bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        res = ts_mod.submit_and_close(app, ts_mod.soroban_tx(
            app, master, ts_mod.invoke_op(
                cid, "sig_demo",
                [mkb(sk.public_key().raw), mkb(msg), mkb(bad)]), ro, rw))
        assert res.result.result.disc.name == "txFAILED", res
    finally:
        ts_mod.COUNTER_CODE = old


def test_u256_i256_env_family(app):
    """The 256-bit host-fn families vs python-int oracles: pieces and
    be-bytes round trips, checked arithmetic (overflow / div0 / shift
    >=256 error), Euclidean remainder, arithmetic right shift
    (reference embeds these via the bridge, rust/src/contract.rs +
    Cargo.toml:27-56)."""
    from stellar_core_tpu.soroban.host import HostError

    ltx, host, ectx, fns = _table_ctx(app)
    try:
        inst = _FakeInst()
        u32 = lambda n: (n << 4) | env_abi.TAG_U32
        M64 = (1 << 64) - 1
        U256_MAX = (1 << 256) - 1

        def u256(x):
            return fns[("i", "B")](inst, (x >> 192) & M64,
                                   (x >> 128) & M64, (x >> 64) & M64,
                                   x & M64)

        def u256_val(h):
            v = ectx.get_obj(h)
            assert v.disc == cx.SCValType.SCV_U256
            p = v.value
            return (int(p.hi_hi) << 192) | (int(p.hi_lo) << 128) | \
                (int(p.lo_hi) << 64) | int(p.lo_lo)

        def i256(x):
            u = x & U256_MAX
            return fns[("i", "I")](inst, (u >> 192) & M64,
                                   (u >> 128) & M64, (u >> 64) & M64,
                                   u & M64)

        def i256_val(h):
            v = ectx.get_obj(h)
            assert v.disc == cx.SCValType.SCV_I256
            p = v.value
            u = ((int(p.hi_hi) & M64) << 192) | (int(p.hi_lo) << 128) | \
                (int(p.lo_hi) << 64) | int(p.lo_lo)
            return u - (1 << 256) if u >> 255 else u

        import random
        rng = random.Random(20260801)
        # --- u256 arithmetic vs oracle ---
        for _ in range(40):
            a = rng.getrandbits(256)
            bb = rng.getrandbits(rng.choice([8, 64, 128, 256]))
            assert u256_val(fns[("i", "P")](inst, u256(a), u256(bb))) \
                == (a + bb) if a + bb <= U256_MAX else True
            if a + bb > U256_MAX:
                with pytest.raises(HostError):
                    fns[("i", "P")](inst, u256(a), u256(bb))
            if a >= bb:
                assert u256_val(fns[("i", "Q")](inst, u256(a),
                                                u256(bb))) == a - bb
            else:
                with pytest.raises(HostError):
                    fns[("i", "Q")](inst, u256(a), u256(bb))
            if a * bb <= U256_MAX:
                assert u256_val(fns[("i", "R")](inst, u256(a),
                                                u256(bb))) == a * bb
            if bb:
                assert u256_val(fns[("i", "S")](inst, u256(a),
                                                u256(bb))) == a // bb
                assert u256_val(fns[("i", "T")](inst, u256(a),
                                                u256(bb))) == a % bb
        with pytest.raises(HostError):
            fns[("i", "S")](inst, u256(1), u256(0))     # div by zero
        with pytest.raises(HostError):
            fns[("i", "R")](inst, u256(1 << 200), u256(1 << 200))
        # pow / shl / shr
        assert u256_val(fns[("i", "U")](inst, u256(3), u32(100))) \
            == 3 ** 100
        with pytest.raises(HostError):
            fns[("i", "U")](inst, u256(2), u32(256))    # overflow
        assert u256_val(fns[("i", "V")](inst, u256(1), u32(255))) \
            == 1 << 255
        assert u256_val(fns[("i", "W")](inst, u256(1 << 255),
                                        u32(200))) == 1 << 55
        for name in ("V", "W"):
            with pytest.raises(HostError):
                fns[("i", name)](inst, u256(1), u32(256))
        # be-bytes round trip
        x = rng.getrandbits(256)
        bh = fns[("i", "D")](inst, u256(x))
        assert bytes(ectx.get_obj(bh).value) == x.to_bytes(32, "big")
        assert u256_val(fns[("i", "C")](inst, bh)) == x
        # pieces getters
        h = u256(x)
        got = [fns[("i", nm)](inst, h) for nm in "EFGH"]
        assert got == [(x >> s) & M64 for s in (192, 128, 64, 0)]

        # --- i256 ---
        I_MIN, I_MAX = -(1 << 255), (1 << 255) - 1
        for _ in range(40):
            a = rng.getrandbits(255) - (1 << 254)
            bb = rng.getrandbits(128) - (1 << 127)
            assert i256_val(fns[("i", "X")](inst, i256(a),
                                            i256(bb))) == a + bb
            assert i256_val(fns[("i", "Y")](inst, i256(a),
                                            i256(bb))) == a - bb
            if I_MIN <= a * bb <= I_MAX:
                assert i256_val(fns[("i", "Z")](inst, i256(a),
                                                i256(bb))) == a * bb
            if bb:
                q = abs(a) // abs(bb)
                if (a < 0) != (bb < 0):
                    q = -q
                assert i256_val(fns[("i", "a")](inst, i256(a),
                                                i256(bb))) == q
                r = a % abs(bb)
                assert i256_val(fns[("i", "b")](inst, i256(a),
                                                i256(bb))) == r
                assert r >= 0
        with pytest.raises(HostError):                  # overflow
            fns[("i", "X")](inst, i256(I_MAX), i256(1))
        with pytest.raises(HostError):                  # MIN / -1
            fns[("i", "a")](inst, i256(I_MIN), i256(-1))
        # arithmetic right shift sign-extends
        assert i256_val(fns[("i", "e")](inst, i256(-8), u32(2))) == -2
        assert i256_val(fns[("i", "e")](inst, i256(I_MIN),
                                        u32(255))) == -1
        # i256 be-bytes round trip (negative)
        nh = fns[("i", "K")](inst, i256(-12345))
        assert bytes(ectx.get_obj(nh).value) == \
            (-12345).to_bytes(32, "big", signed=True)
        assert i256_val(fns[("i", "J")](inst, nh)) == -12345
        # i256 pieces: hi_hi is the SIGNED limb
        hp = i256(-1)
        assert all(fns[("i", nm)](inst, hp) == M64 for nm in "LMNO")

        # duration round trip
        dh = fns[("i", "f")](inst, 86400)
        assert ectx.get_obj(dh).disc == cx.SCValType.SCV_DURATION
        assert fns[("i", "g")](inst, dh) == 86400
    finally:
        ltx.rollback()


def test_env_u256_contract_end_to_end(app):
    """A hand-assembled env-ABI contract computing with u256/i256
    through upload -> create -> invoke (the VERDICT r04 #5 'done'
    condition)."""
    from stellar_core_tpu.soroban.env_contract import build_env_u256
    import test_soroban as ts_mod

    old = ts_mod.COUNTER_CODE
    ts_mod.COUNTER_CODE = build_env_u256()
    try:
        master, cid = ts_mod.deploy(app)
        ro, rw = ts_mod.invoke_footprints(cid)
        res = ts_mod.submit_and_close(app, ts_mod.soroban_tx(
            app, master, ts_mod.invoke_op(cid, "u256_demo"), ro, rw))
        assert res.result.result.disc.name == "txSUCCESS", res
        # the host-fn return value travels in sorobanMeta (V3 meta)
        from stellar_core_tpu.xdr.ledger import TransactionMeta
        row = app.database.query_one(
            "SELECT txmeta FROM txhistory WHERE txid=?",
            (bytes(res.transactionHash),))
        ret = TransactionMeta.from_bytes(
            bytes(row[0])).value.sorobanMeta.returnValue
        assert ret.disc == cx.SCValType.SCV_VEC and len(ret.value) == 2
        uv, iv = ret.value
        assert uv.disc == cx.SCValType.SCV_U256
        got = (int(uv.value.hi_hi) << 192) | (int(uv.value.hi_lo) << 128) \
            | (int(uv.value.lo_hi) << 64) | int(uv.value.lo_lo)
        assert got == (((1 << 192) + (2 << 128) + (3 << 64) + 9) << 7)
        assert iv.disc == cx.SCValType.SCV_I256
        u = ((int(iv.value.hi_hi) & ((1 << 64) - 1)) << 192) | \
            (int(iv.value.hi_lo) << 128) | \
            (int(iv.value.lo_hi) << 64) | int(iv.value.lo_lo)
        assert u - (1 << 256) == -(1 << 255) >> 3
        # checked division: div-by-zero becomes a failed tx, not a wrong
        # answer
        res = ts_mod.submit_and_close(app, ts_mod.soroban_tx(
            app, master, ts_mod.invoke_op(cid, "div_zero"), ro, rw))
        assert res.result.result.disc.name == "txFAILED", res
    finally:
        ts_mod.COUNTER_CODE = old
