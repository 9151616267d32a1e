"""The smart-contract host: storage, budget, auth, and execution.

Reference: the `e2e_invoke::invoke_host_function` surface of
soroban-env-host used by the reference node (rust/src/contract.rs:261-456
adapts it; transactions/InvokeHostFunctionOpFrame.cpp:364 drives it).
This is a native re-implementation of that surface: footprint-gated
storage over LedgerTxn, deterministic instruction budgeting, TTL
liveness, nonce-consuming address authorization (signatures routed
through the node's verifier seam — north-star config #4), contract
events, and host-function dispatch.

Execution is pluggable through `VM_REGISTRY`: production wasm engines
register by code prefix. The built-in `SCVM` interpreter executes a
deterministic SCVal-encoded expression language (each exported function
is one metered expression tree) — it exists so every protocol mechanism
around execution (footprints, rent, TTL, auth, events, budget, fees) is
fully exercised end-to-end; swapping in a wasm engine touches only this
seam.
"""

from __future__ import annotations

import struct
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..crypto.sha import sha256
from ..util.logging import get_logger
from ..xdr.contract import (ContractCodeEntry, ContractDataDurability,
                            ContractDataEntry, ContractEvent,
                            ContractExecutable, ContractExecutableType,
                            ContractIDPreimageType, HostFunction,
                            HostFunctionType, LedgerFootprint, SCAddress,
                            SCAddressType, SCContractInstance, SCError,
                            SCErrorCode, SCErrorType, SCMapEntry,
                            SCNonceKey, SCVal, SCValType, TTLEntry,
                            _ContractEventBody, _ContractEventV0)
from ..xdr.ledger_entries import (LedgerEntry, LedgerEntryType, LedgerKey,
                                  _LedgerEntryData, _LedgerEntryExt)
from ..xdr.types import EnvelopeType, ExtensionPoint, PublicKey

log = get_logger("Tx")


class HostError(Exception):
    def __init__(self, error_type: SCErrorType, code_or_msg="", code=None):
        super().__init__(f"{error_type.name}: {code_or_msg}")
        self.error_type = error_type
        self.code = code


class BudgetExceeded(HostError):
    def __init__(self):
        super().__init__(SCErrorType.SCE_BUDGET, "instruction limit")


class Budget:
    """Deterministic instruction metering (reference: soroban budget)."""

    def __init__(self, instruction_limit: int):
        self.limit = instruction_limit
        self.used = 0

    def charge(self, n: int) -> None:
        self.used += n
        if self.used > self.limit:
            raise BudgetExceeded()


# cost constants (deterministic; roughly scaled to the reference's
# per-operation cost types). Module-level values are the CURRENT
# protocol's calibration; the host classes carry them as class
# attributes so the protocol-prev host can override (see
# host_for_protocol below).
COST_BASE_INSTRUCTION = 100
COST_STORAGE_OP = 5000
COST_PER_BYTE = 10
COST_CALL = 10000
COST_VERIFY_SIG = 400_000


def contract_id_from_preimage(network_id: bytes, preimage) -> bytes:
    """SHA256(HashIDPreimage ENVELOPE_TYPE_CONTRACT_ID) (reference:
    Stellar-transaction.x HashIDPreimage)."""
    return sha256(network_id
                  + struct.pack(">i", EnvelopeType.ENVELOPE_TYPE_CONTRACT_ID)
                  + preimage.to_bytes())


def soroban_auth_payload(network_id: bytes, nonce: int,
                         expiration: int, invocation) -> bytes:
    """Signature payload for address credentials (reference:
    HashIDPreimage ENVELOPE_TYPE_SOROBAN_AUTHORIZATION)."""
    return sha256(
        network_id
        + struct.pack(">i",
                      EnvelopeType.ENVELOPE_TYPE_SOROBAN_AUTHORIZATION)
        + struct.pack(">q", nonce) + struct.pack(">I", expiration)
        + invocation.to_bytes())


def instance_key(contract: SCAddress) -> LedgerKey:
    return LedgerKey.contract_data(
        contract, SCVal(SCValType.SCV_LEDGER_KEY_CONTRACT_INSTANCE),
        ContractDataDurability.PERSISTENT)


def ttl_key_for(key: LedgerKey) -> LedgerKey:
    return LedgerKey.ttl(sha256(key.to_bytes()))


def nonce_key(address: SCAddress, nonce: int) -> LedgerKey:
    """The nonce entry an address-credential authorization of `address`
    consumes: temporary contract data under the address itself."""
    return LedgerKey.contract_data(
        address, SCVal(SCValType.SCV_LEDGER_KEY_NONCE,
                       SCNonceKey(nonce=nonce)),
        ContractDataDurability.TEMPORARY)


# --- pluggable execution -----------------------------------------------------

# code-prefix -> callable(host, contract_addr, code, fn_name, args) -> SCVal
VM_REGISTRY: Dict[bytes, Callable] = {}


def register_vm(prefix: bytes):
    def deco(fn):
        VM_REGISTRY[prefix] = fn
        return fn
    return deco


class SorobanApplyStats:
    """What a ledger's Soroban operations did, and what they share, in
    plain attributes (the sites are per transaction and per signature):
    a `LedgerManager` hangs one on its ledger root, the Soroban
    operation frames reach it through `ltx.get_root()`, and `publish`
    turns the counts into zones and counters once a close.

    `soroban.invoke` (zone): the host function calls, wall seconds;
    `soroban.auth` (zone): the checks of address credentials inside
    them (expiration, signer, signature, nonce consumption);
    `soroban.auth.verify.prevalidated` / `.fallback`: auth signatures
    answered by a verdict table that knew the tuple, and verified by
    the fallback (a miss of the table, or no table);
    `soroban.auth.entries.address` / `.source`: the authorization
    entries of the invocations by credential type;
    `soroban.auth.failed`: `require_auth` calls that raised;
    `soroban.config.load`: network configurations built from the
    ledger's CONFIG_SETTING entries (one a close that applies a Soroban
    operation: see `config`).

    `config` is what the Soroban operations of one close share: None
    outside a close's apply loop, where every reader builds its own
    from its ledger; `UNREAD` from the loop's start; the close's one
    `SorobanNetworkConfig` from its first Soroban operation
    (`soroban/ops.py` `_load_config`) until the loop ends or raises,
    which is before the close's upgrades run. Nothing of one close
    reaches the next."""

    UNREAD = object()

    __slots__ = ("invoke_s", "invokes", "auth_s", "auth_checks",
                 "prevalidated", "fallback", "address_entries",
                 "source_entries", "failed", "config_loads", "config")

    def __init__(self):
        self.config = None
        self.reset()

    def reset(self) -> None:
        self.invoke_s = self.auth_s = 0.0
        self.invokes = self.auth_checks = 0
        self.prevalidated = self.fallback = 0
        self.address_entries = self.source_entries = self.failed = 0
        self.config_loads = 0

    def publish(self, metrics, perf) -> None:
        """Add what was counted since the last call to `perf`'s zones
        and `metrics`' counters, and start again from zero."""
        if not (self.invokes or self.config_loads):
            return
        if perf is not None and self.invokes:
            perf.add("soroban.invoke", self.invoke_s, self.invokes)
            perf.add("soroban.auth", self.auth_s, self.auth_checks)
        if metrics is not None:
            metrics.new_counter("soroban.auth.verify.prevalidated").inc(
                self.prevalidated)
            metrics.new_counter("soroban.auth.verify.fallback").inc(
                self.fallback)
            metrics.new_counter("soroban.auth.entries.address").inc(
                self.address_entries)
            metrics.new_counter("soroban.auth.entries.source").inc(
                self.source_entries)
            metrics.new_counter("soroban.auth.failed").inc(self.failed)
            metrics.new_counter("soroban.config.load").inc(
                self.config_loads)
        self.reset()


class SorobanHost:
    # current-protocol cost calibration (class attrs: the prev host
    # overrides — reference analogue: two complete soroban-env-host
    # versions linked side by side, rust/Cargo.toml:27-56)
    COST_BASE_INSTRUCTION = COST_BASE_INSTRUCTION
    COST_STORAGE_OP = COST_STORAGE_OP
    COST_PER_BYTE = COST_PER_BYTE
    COST_CALL = COST_CALL
    COST_VERIFY_SIG = COST_VERIFY_SIG

    def __init__(self, ltx, header, config, footprint: LedgerFootprint,
                 budget: Budget, network_id: bytes,
                 source_account: PublicKey, verify=None, stats=None):
        self.ltx = ltx
        self.stats = stats if stats is not None else SorobanApplyStats()
        self.header = header
        self.config = config
        self.budget = budget
        self.network_id = network_id
        self.source_account = source_account
        self.verify = verify
        self.events: List[ContractEvent] = []
        self.diagnostics: List[tuple] = []   # (msg bytes, [SCVal]) from log
        self.read_bytes = 0
        self.write_bytes = 0
        self.rent_changes: List[dict] = []
        self._ro = {k.to_bytes() for k in footprint.readOnly}
        self._rw = {k.to_bytes() for k in footprint.readWrite}
        self._auth_entries: List = []
        self._authorized_addrs: List[bytes] = []
        self._call_depth = 0
        self._frame_stack: List[bytes] = []   # executing contract addrs
        self._prng_frames = 0

    # ------------------------------------------------------------- storage --
    def _check_footprint(self, key: LedgerKey, write: bool) -> None:
        kb = key.to_bytes()
        if write:
            if kb not in self._rw:
                raise HostError(SCErrorType.SCE_STORAGE,
                                "write outside footprint")
        elif kb not in self._ro and kb not in self._rw:
            raise HostError(SCErrorType.SCE_STORAGE,
                            "read outside footprint")

    def _is_live(self, key: LedgerKey) -> bool:
        ttl_le = self.ltx.load_without_record(ttl_key_for(key))
        if ttl_le is None:
            return False
        return ttl_le.data.value.liveUntilLedgerSeq >= self.header.ledgerSeq

    def load_entry(self, key: LedgerKey,
                   need_live: bool = True) -> Optional[LedgerEntry]:
        self.budget.charge(self.COST_STORAGE_OP)
        self._check_footprint(key, write=False)
        le = self.ltx.load_without_record(key)
        if le is None:
            return None
        size = len(le.to_bytes())
        self.budget.charge(size * self.COST_PER_BYTE)
        self.read_bytes += size
        if need_live and key.disc in (LedgerEntryType.CONTRACT_DATA,
                                      LedgerEntryType.CONTRACT_CODE) \
                and not self._is_live(key):
            raise HostError(SCErrorType.SCE_STORAGE, "entry archived")
        return le

    def put_entry(self, key: LedgerKey, entry: LedgerEntry,
                  durability=ContractDataDurability.PERSISTENT) -> None:
        self.budget.charge(self.COST_STORAGE_OP)
        self._check_footprint(key, write=True)
        size = len(entry.to_bytes())
        self.budget.charge(size * self.COST_PER_BYTE)
        self.write_bytes += size
        entry.lastModifiedLedgerSeq = self.header.ledgerSeq
        old = self.ltx.load(key)
        if old is not None:
            old_size = len(old.to_bytes())
            self.ltx.erase(key)
            self.ltx.create(entry)
        else:
            old_size = 0
            self.ltx.create(entry)
        self._ensure_ttl(key, durability, old_size, size)

    def erase_entry(self, key: LedgerKey) -> None:
        self.budget.charge(self.COST_STORAGE_OP)
        self._check_footprint(key, write=True)
        if self.ltx.load(key) is not None:
            self.ltx.erase(key)
            ttlk = ttl_key_for(key)
            if self.ltx.load(ttlk) is not None:
                self.ltx.erase(ttlk)

    def _ensure_ttl(self, key: LedgerKey, durability, old_size: int,
                    new_size: int) -> None:
        sa = self.config.state_archival
        is_persistent = durability == ContractDataDurability.PERSISTENT
        min_ttl = sa.minPersistentTTL if is_persistent \
            else sa.minTemporaryTTL
        ttlk = ttl_key_for(key)
        ttl_le = self.ltx.load(ttlk)
        target = self.header.ledgerSeq + min_ttl - 1
        if ttl_le is None:
            self.ltx.create(LedgerEntry(
                lastModifiedLedgerSeq=self.header.ledgerSeq,
                data=_LedgerEntryData(
                    LedgerEntryType.TTL,
                    TTLEntry(keyHash=sha256(key.to_bytes()),
                             liveUntilLedgerSeq=target)),
                ext=_LedgerEntryExt(0)))
            self.rent_changes.append({
                "is_persistent": is_persistent,
                "old_size_bytes": old_size, "new_size_bytes": new_size,
                "old_live_until": 0, "new_live_until": target})
        else:
            old_until = ttl_le.data.value.liveUntilLedgerSeq
            if new_size > old_size:
                self.rent_changes.append({
                    "is_persistent": is_persistent,
                    "old_size_bytes": old_size,
                    "new_size_bytes": new_size,
                    "old_live_until": old_until,
                    "new_live_until": old_until})

    def set_ttl(self, key: LedgerKey, live_until: int) -> None:
        """Pin an entry's liveUntil to an exact ledger (clamped to
        maxEntryTTL) — used where the TTL itself carries protocol
        meaning, e.g. SAC allowance expirations and auth nonces.
        Extensions are rent-charged like any other TTL change and the
        entry must sit in the write footprint like any other write."""
        self.budget.charge(self.COST_STORAGE_OP)
        self._check_footprint(key, write=True)
        ttl_le = self.ltx.load(ttl_key_for(key))
        if ttl_le is None:
            raise HostError(SCErrorType.SCE_STORAGE, "no TTL entry",
                            SCErrorCode.SCEC_MISSING_VALUE)
        sa = self.config.state_archival
        cur = ttl_le.data.value.liveUntilLedgerSeq
        new_until = min(live_until, self.header.ledgerSeq + sa.maxEntryTTL)
        if new_until == cur:
            return
        ttl_le.data.value.liveUntilLedgerSeq = new_until
        if new_until > cur:     # extensions pay rent; shrinks refund none
            le = self.ltx.load_without_record(key)
            size = len(le.to_bytes()) if le is not None else 0
            is_persistent = key.disc == LedgerEntryType.CONTRACT_CODE or \
                key.value.durability == ContractDataDurability.PERSISTENT
            self.rent_changes.append({
                "is_persistent": is_persistent,
                "old_size_bytes": size, "new_size_bytes": size,
                "old_live_until": cur, "new_live_until": new_until})

    def extend_entry_ttl(self, key: LedgerKey, threshold: int,
                         extend_to: int) -> None:
        """Host-function TTL extension (reference: the env's
        extend_contract_data_ttl / extend_current_contract_instance...
        host fns; op-level analogue ExtendFootprintTTLOpFrame above):
        when the entry's remaining TTL is <= threshold, raise its
        liveUntil to ledgerSeq + extend_to (clamped to maxEntryTTL);
        no-op when already above the threshold. Archived entries error
        (they need RestoreFootprint)."""
        if threshold > extend_to:
            raise HostError(SCErrorType.SCE_STORAGE,
                            "threshold > extend_to",
                            SCErrorCode.SCEC_INVALID_INPUT)
        self.budget.charge(self.COST_STORAGE_OP)
        self._check_footprint(key, write=False)
        le = self.ltx.load_without_record(key)
        ttlk = ttl_key_for(key)
        # decide on the UNRECORDED snapshot: a recorded load stamps
        # lastModifiedLedgerSeq into the delta, so a no-op extension
        # would still rewrite the TTL entry at commit and diverge the
        # ledger hash from nodes that never saw the attempt
        ttl_snap = self.ltx.load_without_record(ttlk)
        if le is None or ttl_snap is None or \
                ttl_snap.data.value.liveUntilLedgerSeq < self.header.ledgerSeq:
            raise HostError(SCErrorType.SCE_STORAGE,
                            "missing or archived entry",
                            SCErrorCode.SCEC_MISSING_VALUE)
        size = len(le.to_bytes())
        self.budget.charge(size * self.COST_PER_BYTE)
        cur = ttl_snap.data.value.liveUntilLedgerSeq
        if cur - self.header.ledgerSeq > threshold:
            return
        sa = self.config.state_archival
        new_until = self.header.ledgerSeq + min(extend_to, sa.maxEntryTTL)
        if new_until <= cur:
            return
        is_persistent = key.disc == LedgerEntryType.CONTRACT_CODE or \
            key.value.durability == ContractDataDurability.PERSISTENT
        ttl_le = self.ltx.load(ttlk)            # now we really write
        ttl_le.data.value.liveUntilLedgerSeq = new_until
        self.rent_changes.append({
            "is_persistent": is_persistent,
            "old_size_bytes": size, "new_size_bytes": size,
            "old_live_until": cur, "new_live_until": new_until})

    def log_diagnostic(self, msg: bytes, vals) -> None:
        """Diagnostic log sink (reference: the env's
        log_from_linear_memory emits DIAGNOSTIC contract events);
        recorded off the consensus state — never hashed."""
        self.budget.charge(len(msg) + 8 * len(vals))
        self.diagnostics.append((bytes(msg), list(vals)))

    def get_verify(self):
        """The signature-verifier seam shared by address-credential auth
        and the env's verify_sig_ed25519 host fn: the injected verifier
        (prevalidated-batch routing in catchup/herder) or the sync
        default."""
        if self.verify is not None:
            return self.verify
        from ..tx.signature_checker import default_verify
        return default_verify

    def prng_frame_seed(self, contract_bytes: bytes) -> bytes:
        """Per-invocation-frame prng seed: every validator derives the
        identical stream for a given frame, but two frames — a repeated
        cross-contract call in one tx, or two txs in one ledger — get
        distinct streams (the real env subseeds each frame from a base
        prng; same determinism contract)."""
        self._prng_frames += 1
        return sha256(self.network_id +
                      int(self.header.ledgerSeq).to_bytes(4, "big") +
                      contract_bytes +
                      self.source_account.to_bytes() +
                      self._prng_frames.to_bytes(8, "big"))

    # ---------------------------------------------------------------- auth --
    def set_auth_entries(self, entries) -> None:
        from ..xdr.contract import SorobanCredentialsType
        self._auth_entries = list(entries)
        address = sum(
            1 for e in self._auth_entries if e.credentials.disc
            == SorobanCredentialsType.SOROBAN_CREDENTIALS_ADDRESS)
        self.stats.address_entries += address
        self.stats.source_entries += len(self._auth_entries) - address

    def require_auth(self, address: SCAddress) -> None:
        """reference: host's require_auth — source-account credentials
        authorize the tx source implicitly; address credentials carry a
        signature over the nonce'd invocation payload."""
        ab = address.to_bytes()
        if ab in self._authorized_addrs:
            return
        # invoker authorization (reference: the host treats the DIRECT
        # calling contract as authorized for its own address — contract
        # C calling token.transfer(from=C, ..) needs no auth entry)
        if len(self._frame_stack) >= 2 and self._frame_stack[-2] == ab:
            return
        from ..xdr.contract import SorobanCredentialsType
        for entry in self._auth_entries:
            cred = entry.credentials
            if cred.disc == \
                    SorobanCredentialsType.SOROBAN_CREDENTIALS_SOURCE_ACCOUNT:
                if address.disc == SCAddressType.SC_ADDRESS_TYPE_ACCOUNT \
                        and address.value.to_bytes() == \
                        self.source_account.to_bytes():
                    self._authorized_addrs.append(ab)
                    return
            else:
                ac = cred.value
                if ac.address.to_bytes() != ab:
                    continue
                stats = self.stats
                t0 = time.perf_counter()
                try:
                    self._verify_address_credentials(entry, ac)
                except HostError:
                    stats.failed += 1
                    raise
                finally:
                    stats.auth_s += time.perf_counter() - t0
                    stats.auth_checks += 1
                self._authorized_addrs.append(ab)
                return
        self.stats.failed += 1
        raise HostError(SCErrorType.SCE_AUTH, "no authorization",
                        SCErrorCode.SCEC_INVALID_ACTION)

    def _verify_address_credentials(self, entry, ac) -> None:
        if ac.signatureExpirationLedger < self.header.ledgerSeq:
            raise HostError(SCErrorType.SCE_AUTH, "signature expired")
        if ac.address.disc != SCAddressType.SC_ADDRESS_TYPE_ACCOUNT:
            raise HostError(SCErrorType.SCE_AUTH,
                            "contract-address auth requires __check_auth")
        payload = soroban_auth_payload(
            self.network_id, ac.nonce, ac.signatureExpirationLedger,
            entry.rootInvocation)
        account_raw = bytes(ac.address.value.value)
        sigs = self._extract_signatures(ac.signature)
        if not sigs:
            raise HostError(SCErrorType.SCE_AUTH, "missing signature")
        self.budget.charge(self.COST_VERIFY_SIG * len(sigs))
        verify = self.get_verify()
        stats = self.stats
        for pub, sig in sigs:
            if pub != account_raw:
                raise HostError(SCErrorType.SCE_AUTH,
                                "signer is not the address")
            # a verdict table counts what it answered itself (`hits` of
            # a PrevalidatedVerifier); anything else is the fallback's
            hits = getattr(verify, "hits", None)
            ok = verify(pub, sig, payload)
            if hits is not None and verify.hits != hits:
                stats.prevalidated += 1
            else:
                stats.fallback += 1
            if not ok:
                raise HostError(SCErrorType.SCE_AUTH, "bad signature")
        self._consume_nonce(ac)

    @staticmethod
    def _extract_signatures(sig_val: SCVal) -> List[Tuple[bytes, bytes]]:
        """Signature SCVal: vec of maps {public_key, signature}
        (reference: the account contract's signature format)."""
        out = []
        vals = []
        if sig_val.disc == SCValType.SCV_VEC and sig_val.value:
            vals = list(sig_val.value)
        elif sig_val.disc == SCValType.SCV_MAP:
            vals = [sig_val]
        for v in vals:
            if v.disc != SCValType.SCV_MAP or not v.value:
                continue
            entry = {}
            for me in v.value:
                if me.key.disc == SCValType.SCV_SYMBOL:
                    entry[bytes(me.key.value)] = me.val
            pk = entry.get(b"public_key")
            sg = entry.get(b"signature")
            # only well-typed byte payloads count; anything else is a
            # malformed signature map and is skipped (the caller then
            # raises the auth error) — never a crash, since this also
            # runs in the untrusted validation path
            if pk is not None and sg is not None \
                    and pk.disc == SCValType.SCV_BYTES \
                    and sg.disc == SCValType.SCV_BYTES:
                out.append((bytes(pk.value), bytes(sg.value)))
        return out

    def _consume_nonce(self, ac) -> None:
        """Replay protection: the nonce entry must not exist yet
        (reference: nonce consumption in soroban auth)."""
        key = nonce_key(ac.address, ac.nonce)
        if self.ltx.load_without_record(key) is not None:
            raise HostError(SCErrorType.SCE_AUTH, "nonce already used")
        self.ltx.create(LedgerEntry(
            lastModifiedLedgerSeq=self.header.ledgerSeq,
            data=_LedgerEntryData(
                LedgerEntryType.CONTRACT_DATA,
                ContractDataEntry(
                    ext=ExtensionPoint(0), contract=ac.address,
                    key=SCVal(SCValType.SCV_LEDGER_KEY_NONCE,
                              SCNonceKey(nonce=ac.nonce)),
                    durability=ContractDataDurability.TEMPORARY,
                    val=SCVal(SCValType.SCV_VOID))),
            ext=_LedgerEntryExt(0)))
        ttlk = ttl_key_for(key)
        sa = self.config.state_archival
        self.ltx.create(LedgerEntry(
            lastModifiedLedgerSeq=self.header.ledgerSeq,
            data=_LedgerEntryData(
                LedgerEntryType.TTL,
                TTLEntry(keyHash=sha256(key.to_bytes()),
                         liveUntilLedgerSeq=min(
                             ac.signatureExpirationLedger,
                             self.header.ledgerSeq + sa.maxEntryTTL))),
            ext=_LedgerEntryExt(0)))

    # --------------------------------------------------------------- events --
    def emit_event(self, contract_id: Optional[bytes], topics: List[SCVal],
                   data: SCVal) -> None:
        from ..xdr.contract import ContractEventType
        self.events.append(ContractEvent(
            ext=ExtensionPoint(0), contractID=contract_id,
            type=ContractEventType.CONTRACT,
            body=_ContractEventBody(0, _ContractEventV0(
                topics=topics, data=data))))

    def events_size_bytes(self) -> int:
        return sum(len(e.to_bytes()) for e in self.events)

    # ------------------------------------------------------------- dispatch --
    def invoke_host_function(self, host_fn: HostFunction, auth) -> SCVal:
        stats = self.stats
        t0 = time.perf_counter()
        try:
            return self._invoke_host_function(host_fn, auth)
        finally:
            stats.invoke_s += time.perf_counter() - t0
            stats.invokes += 1

    def _invoke_host_function(self, host_fn: HostFunction, auth) -> SCVal:
        self.set_auth_entries(auth)
        t = host_fn.disc
        if t == HostFunctionType.HOST_FUNCTION_TYPE_UPLOAD_CONTRACT_WASM:
            return self._upload_wasm(bytes(host_fn.value))
        if t == HostFunctionType.HOST_FUNCTION_TYPE_CREATE_CONTRACT:
            return self._create_contract(host_fn.value)
        return self._invoke_contract(host_fn.value)

    def _upload_wasm(self, code: bytes) -> SCVal:
        if len(code) > self.config.max_contract_size:
            raise HostError(SCErrorType.SCE_BUDGET, "code too large",
                            SCErrorCode.SCEC_EXCEEDED_LIMIT)
        code_hash = sha256(code)
        key = LedgerKey.contract_code(code_hash)
        existing = self.ltx.load_without_record(key)
        if existing is None:
            self._check_footprint(key, write=True)
            self.budget.charge(self.COST_STORAGE_OP
                               + len(code) * self.COST_PER_BYTE)
            self.write_bytes += len(code)
            self.ltx.create(LedgerEntry(
                lastModifiedLedgerSeq=self.header.ledgerSeq,
                data=_LedgerEntryData(
                    LedgerEntryType.CONTRACT_CODE,
                    ContractCodeEntry(ext=ExtensionPoint(0),
                                      hash=code_hash, code=code)),
                ext=_LedgerEntryExt(0)))
            self._ensure_ttl(key, ContractDataDurability.PERSISTENT, 0,
                             len(code))
        return SCVal(SCValType.SCV_BYTES, code_hash)

    def _create_contract(self, args) -> SCVal:
        preimage = args.contractIDPreimage
        from_asset = preimage.disc == \
            ContractIDPreimageType.CONTRACT_ID_PREIMAGE_FROM_ASSET
        is_sac = args.executable.disc == \
            ContractExecutableType.CONTRACT_EXECUTABLE_STELLAR_ASSET
        # the executable kind is bound to the preimage kind (reference:
        # only the host itself instantiates the SAC, and only for an
        # asset preimage; a wasm executable needs an address preimage)
        if from_asset != is_sac:
            raise HostError(SCErrorType.SCE_CONTEXT,
                            "executable does not match preimage kind",
                            SCErrorCode.SCEC_INVALID_INPUT)
        if not from_asset:
            # creating from an address requires that address's auth;
            # anyone may deploy the SAC for an existing asset. A factory
            # contract deploying from its OWN address needs no auth
            # entry (reference: the host skips require_auth when the
            # deployer address is the currently executing contract)
            addr = preimage.value.address
            if not (self._frame_stack and
                    self._frame_stack[-1] == addr.to_bytes()):
                self.require_auth(addr)
        contract_id = contract_id_from_preimage(self.network_id, preimage)
        addr = SCAddress(SCAddressType.SC_ADDRESS_TYPE_CONTRACT,
                         contract_id)
        key = instance_key(addr)
        if self.ltx.load_without_record(key) is not None:
            raise HostError(SCErrorType.SCE_STORAGE,
                            "contract already exists",
                            SCErrorCode.SCEC_EXISTING_VALUE)
        storage = None
        if is_sac:
            storage = self._sac_instance_storage(preimage.value)
        elif args.executable.disc == \
                ContractExecutableType.CONTRACT_EXECUTABLE_WASM:
            code_key = LedgerKey.contract_code(
                bytes(args.executable.value))
            if self.ltx.load_without_record(code_key) is None:
                raise HostError(SCErrorType.SCE_STORAGE,
                                "wasm not uploaded",
                                SCErrorCode.SCEC_MISSING_VALUE)
        inst = ContractDataEntry(
            ext=ExtensionPoint(0), contract=addr,
            key=SCVal(SCValType.SCV_LEDGER_KEY_CONTRACT_INSTANCE),
            durability=ContractDataDurability.PERSISTENT,
            val=SCVal(SCValType.SCV_CONTRACT_INSTANCE,
                      SCContractInstance(executable=args.executable,
                                         storage=storage)))
        self.put_entry(key, LedgerEntry(
            lastModifiedLedgerSeq=self.header.ledgerSeq,
            data=_LedgerEntryData(LedgerEntryType.CONTRACT_DATA, inst),
            ext=_LedgerEntryExt(0)))
        return SCVal(SCValType.SCV_ADDRESS, addr)

    def _invoke_contract(self, args) -> SCVal:
        return self.call_contract(args.contractAddress,
                                  bytes(args.functionName),
                                  list(args.args))

    def call_contract(self, contract: SCAddress, fn: bytes,
                      args: List[SCVal]) -> SCVal:
        self.budget.charge(self.COST_CALL)
        self._call_depth += 1
        self._frame_stack.append(contract.to_bytes())
        if self._call_depth > 10:
            raise HostError(SCErrorType.SCE_CONTEXT, "call depth")
        try:
            inst_le = self.load_entry(instance_key(contract))
            if inst_le is None:
                raise HostError(SCErrorType.SCE_STORAGE,
                                "no such contract",
                                SCErrorCode.SCEC_MISSING_VALUE)
            inst = inst_le.data.value.val.value
            if inst.executable.disc == \
                    ContractExecutableType.CONTRACT_EXECUTABLE_STELLAR_ASSET:
                return self._invoke_sac(contract, inst, fn, args)
            code_key = LedgerKey.contract_code(
                bytes(inst.executable.value))
            code_le = self.load_entry(code_key)
            if code_le is None:
                raise HostError(SCErrorType.SCE_STORAGE, "missing code",
                                SCErrorCode.SCEC_MISSING_VALUE)
            code = bytes(code_le.data.value.code)
            for prefix, vm in VM_REGISTRY.items():
                if code.startswith(prefix):
                    return vm(self, contract, code, fn, args)
            raise HostError(SCErrorType.SCE_WASM_VM,
                            "no VM for code format")
        finally:
            self._call_depth -= 1
            self._frame_stack.pop()

    # ------------------------------------------- built-in stellar asset SAC --
    def _sac_instance_storage(self, asset):
        """Instance storage for a freshly deployed SAC: the asset it
        wraps and (for issued assets) the admin, initially the issuer."""
        from ..xdr.ledger_entries import AssetType
        entries = [SCMapEntry(
            key=SCVal(SCValType.SCV_SYMBOL, b"Asset"),
            val=SCVal(SCValType.SCV_BYTES, asset.to_bytes()))]
        if asset.disc != AssetType.ASSET_TYPE_NATIVE:
            issuer_addr = SCAddress(SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                                    asset.value.issuer)
            entries.append(SCMapEntry(
                key=SCVal(SCValType.SCV_SYMBOL, b"Admin"),
                val=SCVal(SCValType.SCV_ADDRESS, issuer_addr)))
        return entries

    @staticmethod
    def _sac_storage_get(inst, key: bytes):
        for me in (inst.storage or []):
            if me.key.disc == SCValType.SCV_SYMBOL and \
                    bytes(me.key.value) == key:
                return me.val
        return None

    def _invoke_sac(self, contract: SCAddress, inst, fn: bytes,
                    args: List[SCVal]) -> SCVal:
        from ..xdr.ledger_entries import Asset
        from .sac import StellarAssetContract
        asset_val = self._sac_storage_get(inst, b"Asset")
        if asset_val is None:
            raise HostError(SCErrorType.SCE_STORAGE,
                            "SAC instance missing asset",
                            SCErrorCode.SCEC_INTERNAL_ERROR)
        asset = Asset.from_bytes(bytes(asset_val.value))
        admin_val = self._sac_storage_get(inst, b"Admin")
        admin = admin_val.value if admin_val is not None else None
        return StellarAssetContract(self, contract, asset,
                                    admin).invoke(fn, args)

    def sac_set_admin(self, contract: SCAddress,
                      new_admin: SCAddress) -> None:
        """Rewrite the SAC instance's Admin entry (set_admin)."""
        key = instance_key(contract)
        le = self.load_entry(key)
        inst = le.data.value.val.value
        entries = [me for me in (inst.storage or [])
                   if not (me.key.disc == SCValType.SCV_SYMBOL and
                           bytes(me.key.value) == b"Admin")]
        entries.append(SCMapEntry(
            key=SCVal(SCValType.SCV_SYMBOL, b"Admin"),
            val=SCVal(SCValType.SCV_ADDRESS, new_admin)))
        inst.storage = entries
        self.put_entry(key, le)


# --- protocol-keyed host dispatch (curr/prev) -------------------------------

# First protocol whose host uses the CURRENT (recalibrated, cheaper)
# cost model. Reference analogue: the node links two complete host
# versions — soroban-env-host-curr always, -prev feature-gated — and
# routes invocations by the ledger protocol so transition-boundary
# replay is bit-exact (rust/Cargo.toml:27-56, contract.rs dual paths).
FIRST_RECALIBRATED_PROTOCOL = 21


class SorobanHostPrev(SorobanHost):
    """The protocol-20 host: identical semantics, original (pre-
    recalibration) cost model. A borderline instruction budget can
    therefore succeed under the current host and exhaust under this
    one — the real, state-visible divergence catchup must reproduce
    when replaying across the upgrade boundary (the protocol-21 story
    in the reference was exactly a cost recalibration)."""

    COST_STORAGE_OP = 2 * COST_STORAGE_OP
    COST_PER_BYTE = 2 * COST_PER_BYTE
    COST_CALL = 2 * COST_CALL


def host_for_protocol(ledger_version: int):
    """The host implementation for a ledger protocol (reference:
    rust_bridge::invoke_host_function routing between the curr and prev
    soroban-env-host builds by protocol)."""
    if ledger_version < FIRST_RECALIBRATED_PROTOCOL:
        return SorobanHostPrev
    return SorobanHost
