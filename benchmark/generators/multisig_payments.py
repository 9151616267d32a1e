"""Seeded signature-dense payment traffic: `payments.py`'s accounts and
seeded cycle, with every account in one of four signer classes.

| class | signers and thresholds | an envelope carries |
| --- | --- | --- |
| `single` | master key, weight 1 | 1 signature |
| `2of3` | master + 2 signers, weight 1, thresholds 2 | 2, which two drawn from the seed |
| `3of5-bumped` | master + 4 signers, weight 1, thresholds 3 | 3 on the inner envelope, drawn from the seed, wrapped in a fee bump the sponsor signs |
| `limit20` | master + 19 signers (MAX_SIGNERS less one), weight 1, thresholds 20 | 20: `signatures<20>` full |

An account's class is drawn once from the seed in the configuration's
fixed shares, so every seed gives the same work in another order. After
the account creation every multi-signer account installs its signers
and its three thresholds by one SetOptions transaction of its own (one
operation a signer, the thresholds on the last). In the configuration's
rotation ledgers ten `2of3` accounts send, in place of their payment, a
SetOptions transaction that removes one signer and adds a new key, and
sign with the new set from the next ledger on.

Everything is made from the program's XDR classes and keys, as
`payments.py` does; what an envelope should be answered is decided by
`benchmark/reference/multisig_model.py`, which takes the plain
description `describe` gives.
"""

import hashlib

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.xdr.ledger_entries import Asset, AssetType, Signer
from stellar_core_tpu.xdr.transaction import (
    DecoratedSignature, FeeBumpTransaction, FeeBumpTransactionEnvelope,
    Memo, MemoType, Operation, OperationType, PaymentOp, Preconditions,
    PreconditionType, SetOptionsOp, Transaction, TransactionEnvelope,
    TransactionV1Envelope, _FeeBumpInnerTx, _OperationBody, _TxExt)
from stellar_core_tpu.xdr.types import (EnvelopeType, SignerKey,
                                        SignerKeyType)

from benchmark.generators.payments import Account, PaymentTraffic, submit
from benchmark.reference import multisig_model

BASE_FEE = 100


def decorated(key: SecretKey, msg: bytes) -> DecoratedSignature:
    return DecoratedSignature(hint=key.public_key().hint(),
                              signature=key.sign(msg))


def signed_frame(network_id: bytes, source: Account, seq: int, ops: list,
                 keys: list):
    """A v1 envelope of `source` at `seq`, signed by exactly `keys`."""
    tx = Transaction(
        sourceAccount=source.muxed, fee=BASE_FEE * len(ops), seqNum=seq,
        cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE), operations=ops, ext=_TxExt(0))
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    frame = make_frame(env, network_id)
    h = frame.contents_hash()
    frame.signatures.extend(decorated(k, h) for k in keys)
    env.value.signatures = frame.signatures
    return frame


def fee_bump(network_id: bytes, inner, payer: Account, keys: list):
    """`inner` wrapped in a fee bump that `payer` pays and `keys` sign:
    twice the inner bid, for the bump counts as one operation more."""
    fb = FeeBumpTransaction(
        feeSource=payer.muxed, fee=2 * inner.tx.fee,
        innerTx=_FeeBumpInnerTx(EnvelopeType.ENVELOPE_TYPE_TX,
                                inner.envelope.value),
        ext=_TxExt(0))
    env = FeeBumpTransactionEnvelope(tx=fb, signatures=[])
    frame = make_frame(TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP, env), network_id)
    h = frame.contents_hash()
    env.signatures = [decorated(k, h) for k in keys]
    frame.signatures = env.signatures
    return frame


def _signer_op(key_raw: bytes, weight: int, threshold=None) -> Operation:
    return Operation(sourceAccount=None, body=_OperationBody(
        OperationType.SET_OPTIONS, SetOptionsOp(
            inflationDest=None, clearFlags=None, setFlags=None,
            masterWeight=None, lowThreshold=threshold,
            medThreshold=threshold, highThreshold=threshold,
            homeDomain=None, signer=Signer(
                key=SignerKey(SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                              key_raw), weight=weight))))


def describe(frame) -> dict:
    """An envelope in `multisig_model`'s plain terms. Every operation of
    this traffic takes its transaction's source; a payment is checked
    at the medium threshold, a SetOptions that touches signers at the
    high one."""
    def sigs(f):
        return [(bytes(d.hint), bytes(d.signature)) for d in f.signatures]

    def level(op):
        return multisig_model.HIGH \
            if op.body.disc == OperationType.SET_OPTIONS \
            else multisig_model.MEDIUM
    inner = frame.inner if frame.is_fee_bump() else frame
    doc = {"hash": inner.contents_hash(), "signatures": sigs(inner),
           "source": bytes(inner.source_id.value),
           "ops": [(None, level(op)) for op in inner.tx.operations]}
    if frame.is_fee_bump():
        doc["outer"] = {"hash": frame.contents_hash(),
                        "signatures": sigs(frame),
                        "fee_source": bytes(frame.fee_source_id.value)}
    return doc


def signature_count(frame) -> int:
    """Decorated signatures of an envelope, a fee bump's inner ones
    included."""
    n = len(frame.signatures)
    return n + len(frame.inner.signatures) if frame.is_fee_bump() else n


class MultisigTraffic(PaymentTraffic):
    """Accounts, classes, signers and payments of one run, all from
    `seed`. `classes`: {name: {"accounts", "extra_signers", "threshold",
    "bumped"}} of the configuration's deployment."""

    def __init__(self, seed: int, network_id: bytes, dep: dict):
        super().__init__(seed, network_id, dep["accounts"], dep["amounts"],
                         dep["starting_balance"])
        # the accounts that pay each other, and behind them the one
        # that pays the fee bumps' fees
        self.payers = self.accounts
        self.sponsor = Account(SecretKey.from_seed(hashlib.sha256(
            b"benchmark-sponsor-%d" % self.seed).digest()))
        self.accounts = self.payers + [self.sponsor]
        self.classes = dep["classes"]
        names = [name for name, c in self.classes.items()
                 for _ in range(c["accounts"])]
        if len(names) != len(self.payers):
            raise ValueError("the classes' accounts do not add up")
        self._rng.shuffle(names)
        self.class_of = names
        # every key that may sign for account i, the master key first
        self.keys = []
        for i, a in enumerate(self.payers):
            extra = self.classes[names[i]]["extra_signers"]
            self.keys.append([a.key] + [self._signer_key(i, j)
                                        for j in range(extra)])
        # rotation: ledger index (1-based payment ledger) -> accounts
        rot = dep["rotation"]
        pool = [i for i, n in enumerate(names) if n == rot["class"]]
        self._rng.shuffle(pool)
        per = rot["accounts_per_ledger"]
        self.rotating = {
            ledger: pool[k * per:(k + 1) * per]
            for k, ledger in enumerate(rot["payment_ledgers"])}
        self.rotated_out = {}       # account index -> the key removed
        self._fresh_keys = 0

    def _signer_key(self, i: int, j: int) -> SecretKey:
        return SecretKey.from_seed(hashlib.sha256(
            b"benchmark-signer-%d-%d-%d" % (self.seed, i, j)).digest())

    # ---------------------------------------------------------- set-up --
    def fund(self, app, model) -> tuple:
        """Ledgers 2 to 4 of a new node: the tx-set size upgrade, the
        account creation (the sponsor too), then every multi-signer
        account's SetOptions. Returns the transactions of ledger 3
        and those of ledger 4."""
        creation = super().fund(app, model)
        installs = self.install_frames()
        submit(app, installs)
        app.manual_close()
        for f in installs:
            model.set_options(bytes(f.source_id.value),
                              len(f.tx.operations))
        return creation, installs

    def install_frames(self) -> list:
        frames = []
        for i, a in enumerate(self.payers):
            extra = self.keys[i][1:]
            if not extra:
                continue
            threshold = self.classes[self.class_of[i]]["threshold"]
            ops = [_signer_op(k.public_key().raw, 1) for k in extra[:-1]]
            ops.append(_signer_op(extra[-1].public_key().raw, 1, threshold))
            a.seq += 1
            frames.append(signed_frame(self.network_id, a, a.seq, ops,
                                       [a.key]))
        return frames

    # -------------------------------------------------------- payments --
    def _signers_for(self, i: int) -> list:
        need = self.classes[self.class_of[i]]["threshold"]
        keys = self.keys[i]
        return keys if need >= len(keys) else self._rng.sample(keys, need)

    def payment(self, i: int, dst: Account, amount: int, keys=None):
        """Account i's next payment, signed as its class says (or by
        `keys`), fee-bumped where its class is."""
        src = self.payers[i]
        src.seq += 1
        op = Operation(sourceAccount=None, body=_OperationBody(
            OperationType.PAYMENT, PaymentOp(
                destination=dst.muxed,
                asset=Asset(AssetType.ASSET_TYPE_NATIVE), amount=amount)))
        frame = signed_frame(self.network_id, src, src.seq, [op],
                             keys or self._signers_for(i))
        if self.classes[self.class_of[i]]["bumped"]:
            frame = fee_bump(self.network_id, frame, self.sponsor,
                             [self.sponsor.key])
        return frame

    def rotation(self, i: int):
        """Account i removes its last signer and adds a new key, signed
        by two of the keys it still has."""
        src = self.payers[i]
        keys = self.keys[i]
        old = keys[-1]
        self._fresh_keys += 1
        new = self._signer_key(i, 1000 + self._fresh_keys)
        signers = self._rng.sample(keys, 2)
        src.seq += 1
        frame = signed_frame(
            self.network_id, src, src.seq,
            [_signer_op(old.public_key().raw, 0),
             _signer_op(new.public_key().raw, 1)], signers)
        self.keys[i] = keys[:-1] + [new]
        self.rotated_out[i] = old
        return frame

    def next_ledger(self) -> list:
        """[(frame, kind, source index, destination index, amount)] of
        the next payment ledger: `payments.py`'s seeded cycle; kind is
        "pay", "bumped" or "rotate" (amount 0: no payment is sent)."""
        order = list(range(len(self.payers)))
        self._rng.shuffle(order)
        rotating = set(self.rotating.get(self.ledgers_made + 1, ()))
        out = []
        n = len(order)
        for at, i in enumerate(order):
            j = order[(at + 1) % n]
            if i in rotating:
                out.append((self.rotation(i), "rotate", i, j, 0))
                continue
            amount = self._rng.choice(self.amounts)
            frame = self.payment(i, self.payers[j], amount)
            kind = "bumped" if frame.is_fee_bump() else "pay"
            out.append((frame, kind, i, j, amount))
        self.ledgers_made += 1
        return out

    # ------------------------------------------------ what a model needs --
    def model_accounts(self) -> dict:
        """{raw key: multisig_model.Account} as this traffic installed
        and rotated the signers: what the generator did, not what the
        node holds."""
        out = {}
        for i, a in enumerate(self.payers):
            extra = self.keys[i][1:]
            t = self.classes[self.class_of[i]]["threshold"] if extra else 0
            out[a.raw] = multisig_model.Account(
                a.raw, 1, [(k.public_key().raw, 1) for k in extra],
                (t, t, t))
        out[self.sponsor.raw] = multisig_model.Account(self.sponsor.raw)
        return out


def apply_to_model(model, traffic: MultisigTraffic, ledger: list) -> None:
    for frame, kind, i, j, amount in ledger:
        src, dst = traffic.payers[i].raw, traffic.payers[j].raw
        if kind == "rotate":
            model.set_options(src, len(frame.tx.operations))
        elif kind == "bumped":
            model.fee_bump_pay(traffic.sponsor.raw, src, dst, amount)
        else:
            model.pay(src, dst, amount)
