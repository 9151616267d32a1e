"""Threshold signature accounting.

Reference: transactions/SignatureChecker.{h,cpp} — given the tx contents
hash and the envelope's DecoratedSignatures, `check_signature(signers,
needed_weight)` consumes signatures (each may be used once), matching by
the 4-byte hint before any crypto, and sums signer weights until the
threshold is met. `check_all_signatures_used` enforces the reference's
txBAD_AUTH_EXTRA rule.

The verify callable is the TPU seam: by default PubKeyUtils.verify_sig
(cached libsodium-semantics path, crypto/SecretKey.cpp:427); the batch
apply paths can inject a `PrevalidatedVerifier` built from one TPU batch
verify over a whole txset/checkpoint (SURVEY.md §3.3).
"""

from __future__ import annotations

import hashlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..crypto.keys import PubKeyUtils
from ..util import tracing
from ..xdr.types import SignerKey, SignerKeyType
from ..xdr.transaction import DecoratedSignature

VerifyFn = Callable[[bytes, bytes, bytes], bool]  # (pub, sig, msg) -> ok


_UNKNOWN = object()      # a tuple the table was never told of


def default_verify(pub: bytes, sig: bytes, msg: bytes) -> bool:
    return PubKeyUtils.verify_sig(pub, sig, msg)


class PrevalidatedVerifier:
    """Lookup table of (pub, sig, msg) -> bool filled by TPU batch
    verifies; falls back to the sync path on miss (stragglers keep exact
    semantics, SURVEY.md §7 'latency vs batch').

    A table filled chunk by chunk is told first what was dispatched
    (`expect`), so a miss is one of two kinds: `misses_pending` (the
    tuple is on its way: its chunk has not been adopted yet, which is
    what apply outran) and `misses_unknown` (no one made this tuple:
    the resolver missed a candidate).

    `hits` and the misses are plain attributes (the call is per
    signature); the owner calls `publish` when it retires the table."""

    def __init__(self, fallback: VerifyFn = default_verify):
        # key -> verdict, or None while the tuple's chunk is in flight
        self._results: Dict[bytes, Optional[bool]] = {}
        self._fallback = fallback
        self.hits = 0
        self.misses_pending = 0
        self.misses_unknown = 0

    @property
    def misses(self) -> int:
        return self.misses_pending + self.misses_unknown

    @staticmethod
    def _key(pub: bytes, sig: bytes, msg: bytes) -> bytes:
        return hashlib.blake2b(pub + sig + msg, digest_size=32).digest()

    def expect(self, tuples: Sequence[Tuple[bytes, bytes, bytes]]
               ) -> List[bytes]:
        """Note `tuples` as dispatched and not yet answered; returns
        their keys, which `add_results` takes back as `keys` so each
        tuple is hashed once."""
        key = self._key
        keys = [key(p, s, m) for p, s, m in tuples]
        results = self._results
        for k in keys:
            results.setdefault(k, None)
        return keys

    def add_results(self, tuples: Sequence[Tuple[bytes, bytes, bytes]],
                    results: Sequence[bool],
                    keys: Optional[Sequence[bytes]] = None) -> None:
        if keys is None:
            keys = [self._key(p, s, m) for p, s, m in tuples]
        for k, ok in zip(keys, results):
            self._results[k] = bool(ok)

    def __call__(self, pub: bytes, sig: bytes, msg: bytes) -> bool:
        r = self._results.get(self._key(pub, sig, msg), _UNKNOWN)
        if r is _UNKNOWN:
            self.misses_unknown += 1
        elif r is None:
            self.misses_pending += 1
        else:
            self.hits += 1
            return r
        return self._fallback(pub, sig, msg)

    def release(self) -> None:
        """Drop the verdict map; `hits` and the misses stay readable.
        For the owner whose checks are over (a checkpoint's work at its
        end): a catchup over many checkpoints keeps counts and not a
        map a checkpoint."""
        self._results = {}

    def publish(self, metrics) -> None:
        """Add `hits` and the misses to the counters
        `crypto.prevalidated.hit` / `.miss` of `metrics`: the checks
        the batch answered, and those it was asked and had to hand to
        the fallback; `.miss.pending` and `.miss.unknown` split the
        latter. The owner calls it once, when it retires the table."""
        if metrics is None:
            return
        metrics.new_counter("crypto.prevalidated.hit").inc(self.hits)
        metrics.new_counter("crypto.prevalidated.miss").inc(self.misses)
        metrics.new_counter("crypto.prevalidated.miss.pending").inc(
            self.misses_pending)
        metrics.new_counter("crypto.prevalidated.miss.unknown").inc(
            self.misses_unknown)


def signed_payload_hint(pubkey_raw: bytes, payload: bytes) -> bytes:
    """Hint for an ed25519-signed-payload signature: pubkey tail XOR
    the zero-right-padded payload tail (reference:
    SignatureUtils::getSignedPayloadHint)."""
    tail = payload[-4:] if len(payload) >= 4 else payload.ljust(4, b"\x00")
    return bytes(a ^ b for a, b in zip(pubkey_raw[28:], tail))


class SignatureChecker:
    def __init__(self, contents_hash: bytes,
                 signatures: Sequence[DecoratedSignature],
                 verify: VerifyFn = default_verify):
        self.contents_hash = contents_hash
        self.signatures = list(signatures)
        self.used = [False] * len(self.signatures)
        self._verify = verify

    @property
    def verify(self) -> VerifyFn:
        """The verifier every check of this checker asks."""
        return self._verify

    def check_signature(self, signers: List[Tuple[SignerKey, int]],
                        needed_weight: int) -> bool:
        """signers: (signer key, weight). Matches the reference
        SignatureChecker::checkSignature exactly: signatures are marked
        used for txBAD_AUTH_EXTRA bookkeeping but remain matchable by
        LATER checkSignature calls (the same master signature covers both
        the tx-low check and each op-threshold check); within one call a
        matched signer is dropped so it can't double-count; weights clamp
        to 255; PRE_AUTH_TX signers count without consuming a
        signature."""
        # fast path: one ed25519 signer (the overwhelmingly common
        # master-key case) — same semantics as the general loop below,
        # without the per-type group scaffolding
        if len(signers) == 1 and \
                signers[0][0].disc == SignerKeyType.SIGNER_KEY_TYPE_ED25519:
            signer, weight = signers[0]
            for i, ds in enumerate(self.signatures):
                if self._match_ed25519(ds, signer):
                    self.used[i] = True
                    return min(weight, 255) >= needed_weight
            return False

        total = 0
        pending: List[Tuple[SignerKey, int]] = []
        for signer, weight in signers:
            w = min(weight, 255)
            if signer.disc == SignerKeyType.SIGNER_KEY_TYPE_PRE_AUTH_TX:
                if signer.value == self.contents_hash:
                    total += w
                    if total >= needed_weight:
                        return True
            else:
                pending.append((signer, w))

        # reference order: HASH_X pass, then ED25519, then SIGNED_PAYLOAD
        for want_type, match in (
                (SignerKeyType.SIGNER_KEY_TYPE_HASH_X, self._match_hash_x),
                (SignerKeyType.SIGNER_KEY_TYPE_ED25519, self._match_ed25519),
                (SignerKeyType.SIGNER_KEY_TYPE_ED25519_SIGNED_PAYLOAD,
                 self._match_signed_payload)):
            group = [(s, w) for (s, w) in pending if s.disc == want_type]
            for i, ds in enumerate(self.signatures):
                for j, (signer, w) in enumerate(group):
                    if match(ds, signer):
                        self.used[i] = True
                        total += w
                        if total >= needed_weight:
                            return True
                        group.pop(j)
                        break
        # no early return ⇒ threshold never reached; note a call with
        # needed_weight 0 still requires at least one match (reference
        # returns false at the end unconditionally)
        return False

    def _match_ed25519(self, ds: DecoratedSignature,
                       signer: SignerKey) -> bool:
        pub = signer.value
        if ds.hint != pub[28:]:
            return False
        return self._verify(pub, ds.signature, self.contents_hash)

    def _match_signed_payload(self, ds: DecoratedSignature,
                              signer: SignerKey) -> bool:
        sp = signer.value
        if ds.hint != signed_payload_hint(bytes(sp.ed25519),
                                          bytes(sp.payload)):
            return False
        return self._verify(sp.ed25519, ds.signature, sp.payload)

    def _match_hash_x(self, ds: DecoratedSignature,
                      signer: SignerKey) -> bool:
        hash_x = signer.value
        preimage = ds.signature
        if len(preimage) > 64:
            return False
        if hashlib.sha256(preimage).digest() != hash_x:
            return False
        return ds.hint == hash_x[28:]

    def check_all_signatures_used(self) -> bool:
        return all(self.used)


def collect_signature_tuples(frames, network_id=None, ledger_state=None,
                             perf=None, metrics=None, checkpoint=None,
                             carried=None, added=None):
    """(pub, sig, msg) candidates for a batch verify, by signer
    resolution: each decorated signature is paired with EVERY
    hint-matching ed25519 key that could be asked to verify it at
    apply. The candidate keys of an envelope are

    - the keys it names: the transaction's source, its operations'
      sources and, for a fee bump, the fee source (outer signatures
      over the outer hash) and the inner source and operation sources
      (inner signatures over the inner hash);
    - those accounts' ed25519 signers in `ledger_state` (a ledger
      root: one `prefetch` of every named account, then cache reads —
      one bulk read a call, never one an account); callers that pass
      none get the envelope's and the operations' keys only;
    - signer keys that `SetOptions` operations of `frames` add to
      those accounts, anywhere in `frames`: a checkpoint tells the
      resolver the signers it installs and rotates in itself (`added`:
      `signer_adds(frames)` from a caller that has made it already);
    - `carried`, {account: signer keys} that operations parsed and not
      yet applied add (`signer_adds` of their frames): a checkpoint
      collected while the one before it still applies is resolved
      against what is in flight, not only against a state that its
      signers have not reached (catchup/catchup_work.py).

    A tuple is a fact about three byte strings, so a candidate too many
    costs one device lane and a candidate missed is a counted miss of
    the `PrevalidatedVerifier` that falls back to the sync path: exact
    semantics either way (SURVEY.md §7 'latency vs batch'). With
    `network_id`, every Soroban address-credential auth-entry signature
    rides along with its deterministic auth payload (BASELINE.md config
    #4). Tuples come out in the order of `frames`, no key twice for one
    signature. Shared by the herder's txset validation,
    the close's stage prewarm and catchup's checkpoint prevalidation
    (SURVEY.md §3.2/§3.3 collection points).

    `perf` opens the zone `crypto.collectTuples` round the collection
    (args `checkpoint`, `n`, `frames`) and `metrics` counts
    `crypto.collect.signatures` (decorated signatures seen),
    `crypto.collect.candidates` (tuples made),
    `crypto.collect.carried` (those of them whose key `carried` alone
    gave) and `crypto.collect.auth` (those of them that are Soroban
    auth-entry signatures): once a call, so the per-transaction callers
    pass neither."""
    if perf is None:
        return _collect(frames, network_id, ledger_state, metrics, carried,
                        added)
    targs = {"checkpoint": checkpoint, "frames": len(frames)} \
        if tracing.ENABLED else None
    with perf.zone("crypto.collectTuples", targs=targs):
        tuples = _collect(frames, network_id, ledger_state, metrics,
                          carried, added)
        if targs is not None:
            targs["n"] = len(tuples)
    return tuples


def _ed25519_raw(muxed) -> bytes:
    """The 32-byte account key of a MuxedAccount."""
    return bytes(muxed.account_id().value)


def _named_accounts(frame) -> List[bytes]:
    """Raw keys of the accounts whose signers `frame`'s own signatures
    may be checked against: the source and every operation source."""
    named = [bytes(frame.source_id.value)]
    for op in frame.tx.operations:
        if op.sourceAccount is not None:
            raw = _ed25519_raw(op.sourceAccount)
            if raw not in named:
                named.append(raw)
    return named


def signer_adds(frames) -> Dict[bytes, List[bytes]]:
    """{account raw key: ed25519 signer keys that a SetOptions operation
    of `frames` adds to it}."""
    from ..xdr.transaction import OperationType
    added: Dict[bytes, List[bytes]] = {}
    for frame in frames:
        for op in frame.tx.operations:
            if op.body.disc != OperationType.SET_OPTIONS:
                continue
            signer = op.body.value.signer
            if signer is None or not signer.weight or signer.key.disc \
                    != SignerKeyType.SIGNER_KEY_TYPE_ED25519:
                continue
            acct = bytes(frame.source_id.value) \
                if op.sourceAccount is None \
                else _ed25519_raw(op.sourceAccount)
            keys = added.setdefault(acct, [])
            key = bytes(signer.key.value)
            if key not in keys:
                keys.append(key)
    return added


def _state_signers(ledger_state, accounts) -> Dict[bytes, List[bytes]]:
    """{account raw key: its ed25519 signers in `ledger_state`}: one
    bulk prefetch, then reads of the root's cache."""
    from ..xdr.ledger_entries import LedgerKey
    from ..xdr.types import PublicKey
    kbs = {raw: LedgerKey.account(PublicKey.ed25519(raw)).to_bytes()
           for raw in accounts}
    ledger_state.prefetch(kbs.values())
    out: Dict[bytes, List[bytes]] = {}
    for raw, kb in kbs.items():
        le = ledger_state.get_entry(kb)
        if le is None:
            continue
        keys = [bytes(s.key.value) for s in le.data.value.signers
                if s.key.disc == SignerKeyType.SIGNER_KEY_TYPE_ED25519]
        if keys:
            out[raw] = keys
    return out


def _collect(frames, network_id, ledger_state, metrics, carried=None,
             added=None) -> list:
    parts = []          # (signatures, hash, named accounts) per envelope
    for frame in frames:
        if frame.is_fee_bump():
            parts.append((frame.signatures, frame.contents_hash(),
                          [bytes(frame.fee_source_id.value)], None))
            frame = frame.inner
        parts.append((frame.signatures, frame.contents_hash(),
                      _named_accounts(frame), frame))
    if added is None:
        added = signer_adds(frames)
    carried = carried or {}
    carried_alone = set()   # keys that only `carried` gave an account
    in_state = {} if ledger_state is None else _state_signers(
        ledger_state, {a for _, _, named, _ in parts for a in named})
    by_hint: Dict[bytes, Dict[bytes, List[bytes]]] = {}

    def hints_of(acct: bytes) -> Dict[bytes, List[bytes]]:
        """{hint: candidate keys} of one account, made once."""
        table = by_hint.get(acct)
        if table is None:
            table = by_hint[acct] = {}
            own = (acct, *in_state.get(acct, ()), *added.get(acct, ()))
            for n, key in enumerate((*own, *carried.get(acct, ()))):
                keys = table.setdefault(key[-4:], [])
                if key not in keys:
                    keys.append(key)
                    if n >= len(own):
                        carried_alone.add(key)
        return table

    tuples = []
    seen_signatures = from_carry = auth = 0
    for signatures, h, named, frame in parts:
        seen_signatures += len(signatures)
        tables = [hints_of(a) for a in named]
        for ds in signatures:
            hint, sig = bytes(ds.hint), bytes(ds.signature)
            keys = tables[0].get(hint, ())
            if len(tables) > 1:
                keys = list(keys)
                for t in tables[1:]:
                    keys.extend(k for k in t.get(hint, ())
                                if k not in keys)
            for key in keys:
                tuples.append((key, sig, h))
            if carried_alone:
                from_carry += sum(1 for k in keys if k in carried_alone)
        if network_id is not None and frame is not None:
            n = len(tuples)
            tuples.extend(_soroban_auth_tuples(frame, network_id))
            auth += len(tuples) - n
    if metrics is not None:
        metrics.new_counter("crypto.collect.signatures").inc(
            seen_signatures)
        metrics.new_counter("crypto.collect.candidates").inc(len(tuples))
        metrics.new_counter("crypto.collect.carried").inc(from_carry)
        metrics.new_counter("crypto.collect.auth").inc(auth)
    return tuples


def _soroban_auth_tuples(frame, network_id: bytes):
    """Address-credential auth signatures of a tx's InvokeHostFunction
    ops: the payload is deterministic from the envelope alone, so these
    batch ahead of apply exactly like tx signatures."""
    from ..xdr.contract import (SCAddressType, SorobanCredentialsType)
    from ..xdr.transaction import OperationType
    out = []
    for op in frame.tx.operations:      # fee bump shares the inner .tx
        if op.body.disc != OperationType.INVOKE_HOST_FUNCTION:
            continue
        for entry in op.body.value.auth:
            cred = entry.credentials
            if cred.disc != \
                    SorobanCredentialsType.SOROBAN_CREDENTIALS_ADDRESS:
                continue
            ac = cred.value
            if ac.address.disc != SCAddressType.SC_ADDRESS_TYPE_ACCOUNT:
                continue
            from ..soroban.host import SorobanHost, soroban_auth_payload
            payload = soroban_auth_payload(
                network_id, ac.nonce, ac.signatureExpirationLedger,
                entry.rootInvocation)
            for pub, sig in SorobanHost._extract_signatures(ac.signature):
                out.append((pub, sig, payload))
    return out
