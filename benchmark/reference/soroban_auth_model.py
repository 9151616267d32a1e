"""Plain reference for relayed Stellar-Asset-Contract transfers: a
dictionary of balances, sequence numbers and used (address, nonce)
pairs, advanced by the transfers the node is given, in the order the
ledger applied them.

Imports nothing of the program: the XDR of the signed preimage is
written out here by hand, and the signature's verdict is the
pure-Python oracle's (`ed25519_oracle.py`) unless the caller hands in
another verifier for the tuples it does not sample.

The semantics, from the CAPs:

- CAP-0046-06: `transfer(from, to, amount)` of the native asset's
  contract calls `from.require_auth()`, then moves `amount` stroops of
  the account `from` to the account `to`; it fails if `from` lacks
  them.
- CAP-0046-11: an authorization entry with source-account credentials
  authorizes the transaction's source and nothing else. One with
  address credentials authorizes `address` if (a) its
  `signatureExpirationLedger` is not below the ledger being closed,
  (b) the signer named in its signature map is the address's own key,
  (c) that key's Ed25519 signature over SHA-256 of the
  `ENVELOPE_TYPE_SOROBAN_AUTHORIZATION` preimage (network id, nonce,
  expiration, the invocation) verifies, and (d) the pair (address,
  nonce) was never used; it then uses the pair. The checks run in that
  order, so a transfer that fails (a) or (b) asks for no verification
  and one that fails (d) has asked for one.
- CAP-0046-07: the fee is the inclusion fee (the base fee, outside
  surge pricing) and the resource fee. The non-refundable part of the
  resource fee is computed from the declared resources and the
  envelope's size at the network's rates (protocol 20's initial
  settings; `TESTING_SOROBAN_HIGH_LIMIT_OVERRIDE` raises limits, not
  rates); of the refundable part a successful transfer is charged one
  event of under 1 KB and no rent (it writes two accounts, which pay
  none), and gets the rest back.
- a failed transfer moves nothing, takes its source's sequence number
  and keeps the fee: **the whole declared fee in this tree**, which
  refunds nothing to a transaction that failed (upstream refunds the
  refundable part; PERF.md section 7).
"""

import hashlib
import struct
from collections import namedtuple

from benchmark.reference import ed25519_oracle

BASE_FEE = 100

# Stellar-transaction.x / Stellar-contract.x discriminants
ENVELOPE_TYPE_SOROBAN_AUTHORIZATION = 9
SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN = 0
SC_ADDRESS_TYPE_ACCOUNT, SC_ADDRESS_TYPE_CONTRACT = 0, 1
PUBLIC_KEY_TYPE_ED25519 = 0
SCV_I128, SCV_ADDRESS = 10, 18

# CAP-0046-07's rates at protocol 20's initial settings
FEE_PER_INSTRUCTIONS_INCREMENT = 25       # per 10,000 instructions
FEE_READ_LEDGER_ENTRY = 6250
FEE_WRITE_LEDGER_ENTRY = 10000
FEE_READ_1KB = 1786
FEE_WRITE_1KB = 1000                      # an empty bucket list's
FEE_TX_SIZE_1KB = 1624
FEE_HISTORICAL_1KB = 16235
FEE_CONTRACT_EVENTS_1KB = 10000
TTL_ENTRY_SIZE = 48

SUCCESS = "success"
FAILED = "failed"

# One transfer as the generator made it. `credential` is "source" or
# "address"; nonce, expiration, signer and signature are None for the
# first. `resources` is (instructions, read bytes, write bytes, entries
# read-only, entries read-write) as declared.
Transfer = namedtuple("Transfer", (
    "source", "frm", "to", "amount", "credential", "nonce", "expiration",
    "signer", "signature", "inclusion_fee", "resource_fee", "resources",
    "envelope_size"))


def _increments(x: int, unit: int) -> int:
    return (x + unit - 1) // unit


def non_refundable_fee(resources, envelope_size: int) -> int:
    instructions, read_bytes, write_bytes, ro, rw = resources
    return (_increments(instructions, 10_000)
            * FEE_PER_INSTRUCTIONS_INCREMENT
            + (ro + rw) * FEE_READ_LEDGER_ENTRY
            + rw * FEE_WRITE_LEDGER_ENTRY
            + _increments(read_bytes, 1024) * FEE_READ_1KB
            + _increments(write_bytes, 1024) * FEE_WRITE_1KB
            + _increments(envelope_size, 1024) * FEE_TX_SIZE_1KB
            + _increments(envelope_size + TTL_ENTRY_SIZE, 1024)
            * FEE_HISTORICAL_1KB)


def _account_address(raw: bytes) -> bytes:
    return struct.pack(">ii", SC_ADDRESS_TYPE_ACCOUNT,
                       PUBLIC_KEY_TYPE_ED25519) + raw


def auth_payload(network_id: bytes, contract_id: bytes, t) -> bytes:
    """SHA-256 of HashIDPreimage ENVELOPE_TYPE_SOROBAN_AUTHORIZATION for
    `transfer(from, to, amount)` of `contract_id` with no
    sub-invocations: what `from` signs."""
    amount = struct.pack(">iqQ", SCV_I128, t.amount >> 64,
                         t.amount & ((1 << 64) - 1))
    args = (struct.pack(">i", SCV_ADDRESS) + _account_address(t.frm)
            + struct.pack(">i", SCV_ADDRESS) + _account_address(t.to)
            + amount)
    name = b"transfer"                      # 8 bytes: no padding
    invocation = (
        struct.pack(">i", SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN)
        + struct.pack(">i", SC_ADDRESS_TYPE_CONTRACT) + contract_id
        + struct.pack(">I", len(name)) + name
        + struct.pack(">I", 3) + args
        + struct.pack(">I", 0))             # subInvocations<>
    return hashlib.sha256(
        network_id
        + struct.pack(">i", ENVELOPE_TYPE_SOROBAN_AUTHORIZATION)
        + struct.pack(">qI", t.nonce, t.expiration)
        + invocation).digest()


class SorobanAuthModel:
    def __init__(self, network_id: bytes, contract_id: bytes,
                 verify=ed25519_oracle.verify):
        self.network_id = network_id
        self.contract_id = contract_id
        self.verify = verify
        self.balance = {}
        self.seq = {}
        self.used = set()          # (address, nonce) pairs consumed
        self.applied = 0
        self.verified = 0          # signatures a verdict was asked for

    def create(self, account: bytes, balance: int, seq: int) -> None:
        self.balance[account] = balance
        self.seq[account] = seq

    def _authorized(self, ledger_seq: int, t):
        """None, or why `from` did not authorize the transfer."""
        if t.credential == "source":
            return None if t.frm == t.source else "no authorization"
        if t.expiration < ledger_seq:
            return "signature expired"
        if t.signer != t.frm:
            return "signer is not the address"
        self.verified += 1
        if not self.verify(t.signer, t.signature, auth_payload(
                self.network_id, self.contract_id, t)):
            return "bad signature"
        if (t.frm, t.nonce) in self.used:
            return "nonce already used"
        return None

    def apply(self, ledger_seq: int, t) -> tuple:
        """Advance by one transfer of ledger `ledger_seq`; returns
        (SUCCESS, None) or (FAILED, why)."""
        self.seq[t.source] += 1
        self.applied += 1
        fee = t.inclusion_fee + t.resource_fee
        why = self._authorized(ledger_seq, t)
        if why is None and self.balance[t.frm] < t.amount:
            why = "balance is not sufficient"
        if why is not None:
            self.balance[t.source] -= fee
            return FAILED, why
        if t.credential == "address":
            self.used.add((t.frm, t.nonce))
        refundable = t.resource_fee - non_refundable_fee(
            t.resources, t.envelope_size)
        self.balance[t.source] -= fee - (refundable
                                         - FEE_CONTRACT_EVENTS_1KB)
        self.balance[t.frm] -= t.amount
        self.balance[t.to] += t.amount
        return SUCCESS, None

    def differences(self, observed: dict) -> int:
        """How many accounts differ from `observed`
        {account: (balance, seq)}; a missing account differs."""
        return sum(1 for acct, bal in self.balance.items()
                   if observed.get(acct) != (bal, self.seq[acct]))

    def nonce_differences(self, observed) -> int:
        """How many (address, nonce) pairs are used here and not in
        `observed` (a set of such pairs), or there and not here."""
        return len(self.used ^ set(observed))
