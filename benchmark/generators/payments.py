"""Seeded payment traffic, shared by the traffic drivers of this
directory: accounts from the seed, one ledger's payments at a time,
envelopes signed before they are needed.

Copied and repaired from `stellar_core_tpu/simulation/load_generator.py`
(`generate_accounts`, `generate_payments`, `_sign_and_submit`): that
generator seeds from the node id and signs inside the submit call, so
the generator's cost would sit in a timed window. Here every key, every
pairing and every amount is a function of `--seed`, and signing is a
step of its own.

Every seed gives the same work in another order: each ledger every
account sends exactly one payment (so no source ever has two pending
transactions and every sequence number is known in advance); the seed
draws the account keys, who pays whom in each ledger, and each amount
from the fixed list `amounts`.
"""

import hashlib
import random

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.tx_queue import AddResult
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType
from stellar_core_tpu.xdr.transaction import (
    CreateAccountOp, DecoratedSignature, Memo, MemoType, MuxedAccount,
    Operation, OperationType, PaymentOp, Preconditions, PreconditionType,
    Transaction, TransactionEnvelope, TransactionV1Envelope, _OperationBody,
    _TxExt)
from stellar_core_tpu.xdr.types import EnvelopeType, PublicKey

from benchmark.harness import node

CREATE_OPS_PER_TX = 100          # operations per account-creation tx


class Account:
    __slots__ = ("key", "raw", "muxed", "hint", "seq")

    def __init__(self, key: SecretKey):
        self.key = key
        self.raw = key.public_key().raw
        self.muxed = MuxedAccount.from_ed25519(self.raw)
        self.hint = key.public_key().hint()
        self.seq = 0


def _signed_frame(network_id: bytes, source: Account, seq: int, ops: list):
    tx = Transaction(
        sourceAccount=source.muxed, fee=100 * len(ops), seqNum=seq,
        cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE), operations=ops, ext=_TxExt(0))
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    frame = make_frame(env, network_id)
    frame.signatures.append(DecoratedSignature(
        hint=source.hint,
        signature=source.key.sign(frame.contents_hash())))
    env.value.signatures = frame.signatures
    return frame


class PaymentTraffic:
    """Accounts and payments of one run, all from `seed`."""

    def __init__(self, seed: int, network_id: bytes, accounts: int,
                 amounts: list, starting_balance: int):
        self.seed = int(seed)
        self.network_id = network_id
        self.amounts = list(amounts)
        self.starting_balance = int(starting_balance)
        self.root = Account(SecretKey.from_seed(network_id))
        self.accounts = [
            Account(SecretKey.from_seed(hashlib.sha256(
                b"benchmark-account-%d-%d" % (self.seed, i)).digest()))
            for i in range(accounts)]
        self._rng = random.Random(self.seed)
        self.ledgers_made = 0

    # ------------------------------------------------------ creation --
    def creation_frames(self, root_seq: int) -> list:
        """The CreateAccountOp transactions (100 operations each) that
        fan the accounts out of the network root."""
        frames = []
        for at in range(0, len(self.accounts), CREATE_OPS_PER_TX):
            ops = [Operation(sourceAccount=None, body=_OperationBody(
                OperationType.CREATE_ACCOUNT, CreateAccountOp(
                    destination=PublicKey.ed25519(a.raw),
                    startingBalance=self.starting_balance)))
                for a in self.accounts[at:at + CREATE_OPS_PER_TX]]
            root_seq += 1
            frames.append(_signed_frame(self.network_id, self.root,
                                        root_seq, ops))
        return frames

    def fund(self, app, model) -> list:
        """Ledgers 2 and 3 of a new node: the tx-set size upgrade, then
        the account creation. Learns every account's sequence number
        from the node and enters the accounts into `model`. Returns the
        creation transactions."""
        app.manual_close()           # ledger 2: the tx-set size upgrade
        frames = self.creation_frames(node.account_seq(app, self.root.raw))
        submit(app, frames)
        app.manual_close()
        states = node.account_states(app, [a.raw for a in self.accounts])
        if len(states) != len(self.accounts):
            raise RuntimeError("account creation did not apply")
        for a in self.accounts:
            a.seq = states[a.raw][1]
            model.create(a.raw, *states[a.raw])
        return frames

    # ------------------------------------------------------ payments --
    def next_ledger(self) -> list:
        """[(frame, source index, destination index, amount)] of the
        next ledger: a seeded cycle through all accounts, so each
        account sends once and receives once."""
        order = list(range(len(self.accounts)))
        self._rng.shuffle(order)
        out = []
        n = len(order)
        native = Asset(AssetType.ASSET_TYPE_NATIVE)
        for i in range(n):
            src = self.accounts[order[i]]
            dst = self.accounts[order[(i + 1) % n]]
            amount = self._rng.choice(self.amounts)
            src.seq += 1
            op = Operation(sourceAccount=None, body=_OperationBody(
                OperationType.PAYMENT, PaymentOp(
                    destination=dst.muxed, asset=native, amount=amount)))
            out.append((_signed_frame(self.network_id, src, src.seq, [op]),
                        order[i], order[(i + 1) % n], amount))
        self.ledgers_made += 1
        return out


def submit(app, frames) -> None:
    """Set-up's way in: every frame through `herder.recv_transaction`,
    and each must be acknowledged."""
    recv = app.herder.recv_transaction
    for f in frames:
        res = recv(f)
        if res != AddResult.ADD_STATUS_PENDING:
            raise RuntimeError(f"set-up: a transaction was refused: {res}")
