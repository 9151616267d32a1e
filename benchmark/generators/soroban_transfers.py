"""Seeded Stellar-Asset-Contract transfers, relayed and self-signed: the
traffic builder of `soroban_replay.py`, and of any later mix that
carries Soroban transfers beside other transactions.

The envelope shapes are those of `generateload mode=sac_auth`
(`stellar_core_tpu/simulation/load_generator.py`
`generate_sac_transfers(relayed_share=...)`): one
`InvokeHostFunctionOp` a transaction calling `transfer(from, to,
amount)` of the native asset's contract, the declared footprint
(read-only the contract instance; read-write both accounts and, for a
relayed one, the nonce key), that generator's declared resources and
fee. They are built here, as `payments.py` builds its payments, so
that every key, pairing, amount, nonce and fault is a function of
`--seed`, signing is a step of its own, and a program older than that
generator mode can be handed the same traffic.

Each ledger every account is the source of exactly one transaction (so
every sequence number is known in advance). `relayed` of a ledger's
transfers are relayed: source `s`, `from` the next account of the
ledger's seeded cycle, `to` the one after; `from` authorizes with
address credentials that carry its own signature. The rest are
self-signed: `from` is the source, with source-account credentials.
`adversarial` of each kind, all relayed, fail at apply in publisher and
replayer alike:

- `bad_signature`: one bit of the auth signature flipped;
- `nonce_reuse`: the (address, nonce) pair of a transfer of an earlier
  ledger (of this ledger, in the first), signed afresh;
- `expired`: `signatureExpirationLedger` one below the ledger;
- `wrong_signer`: the signature map names, and is signed by, a key that
  is not the address's.
"""

import hashlib
import random

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.soroban.host import (contract_id_from_preimage,
                                           instance_key,
                                           soroban_auth_payload)
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.xdr import contract as cx
from stellar_core_tpu.xdr.ledger_entries import (Asset, AssetType,
                                                 LedgerKey)
from stellar_core_tpu.xdr.transaction import (
    DecoratedSignature, Memo, MemoType, Operation, OperationType,
    Preconditions, PreconditionType, Transaction, TransactionEnvelope,
    TransactionV1Envelope, _OperationBody, _TxExt)
from stellar_core_tpu.xdr.types import EnvelopeType, PublicKey

from benchmark.generators.payments import Account, PaymentTraffic, submit
from benchmark.harness import node
from benchmark.reference.soroban_auth_model import Transfer

KINDS = ("bad_signature", "nonce_reuse", "expired", "wrong_signer")

# the declared resources and fee of `generateload`'s Soroban modes
INSTRUCTIONS = 4_000_000
READ_BYTES = WRITE_BYTES = 50_000
RESOURCE_FEE = 10_000_000
INCLUSION_FEE = 100


def _account_address(raw: bytes):
    return cx.SCAddress(cx.SCAddressType.SC_ADDRESS_TYPE_ACCOUNT,
                        PublicKey.ed25519(raw))


def _i128(v: int):
    return cx.SCVal(cx.SCValType.SCV_I128,
                    cx.Int128Parts(hi=v >> 64, lo=v & ((1 << 64) - 1)))


def nonce_key(address_raw: bytes, nonce: int):
    return LedgerKey.contract_data(
        _account_address(address_raw),
        cx.SCVal(cx.SCValType.SCV_LEDGER_KEY_NONCE,
                 cx.SCNonceKey(nonce=nonce)),
        cx.ContractDataDurability.TEMPORARY)


def _soroban_ext(ro: list, rw: list):
    return _TxExt(1, cx.SorobanTransactionData(
        resources=cx.SorobanResources(
            footprint=cx.LedgerFootprint(readOnly=ro, readWrite=rw),
            instructions=INSTRUCTIONS, readBytes=READ_BYTES,
            writeBytes=WRITE_BYTES),
        resourceFee=RESOURCE_FEE))


def _signed(network_id: bytes, source: Account, seq: int, body, ext):
    tx = Transaction(
        sourceAccount=source.muxed, fee=INCLUSION_FEE + RESOURCE_FEE,
        seqNum=seq, cond=Preconditions(PreconditionType.PRECOND_NONE),
        memo=Memo(MemoType.MEMO_NONE),
        operations=[Operation(sourceAccount=None, body=body)], ext=ext)
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX,
        TransactionV1Envelope(tx=tx, signatures=[]))
    frame = make_frame(env, network_id)
    frame.signatures.append(DecoratedSignature(
        hint=source.hint,
        signature=source.key.sign(frame.contents_hash())))
    env.value.signatures = frame.signatures
    return frame


class SorobanTraffic:
    """Accounts, the contract and the transfers of one run, all from
    `seed`."""

    def __init__(self, seed: int, network_id: bytes, dep: dict):
        self.seed = int(seed)
        self.network_id = network_id
        self.dep = dep
        self.amounts = list(dep["amounts"])
        self.relayed = int(dep["relayed_per_ledger"])
        self.adversarial = int(dep["adversarial_per_kind"])
        self.expiration_ahead = int(dep["signature_expiration_ahead"])
        self._pay = PaymentTraffic(seed, network_id, dep["accounts"],
                                   self.amounts, dep["starting_balance"])
        self.root = self._pay.root
        self.accounts = self._pay.accounts
        self._rng = random.Random(self.seed ^ 0x50AB)
        self.stranger = Account(SecretKey.from_seed(hashlib.sha256(
            b"benchmark-stranger-%d" % self.seed).digest()))
        preimage = cx.ContractIDPreimage(
            cx.ContractIDPreimageType.CONTRACT_ID_PREIMAGE_FROM_ASSET,
            Asset(AssetType.ASSET_TYPE_NATIVE))
        self._preimage = preimage
        self.contract_id = contract_id_from_preimage(network_id, preimage)
        self.contract = cx.SCAddress(
            cx.SCAddressType.SC_ADDRESS_TYPE_CONTRACT, self.contract_id)
        self.consumed = []         # (address raw, nonce) of sound ones
        self.ledgers_made = 0
        self._index = {a.raw: a for a in self.accounts}

    # -------------------------------------------------------- set-up --
    def fund(self, app, model) -> list:
        """Ledgers 2 to 4 of a new node: the tx-set size upgrade, the
        account creation, the deployment of the native asset's
        contract. Returns those transactions."""
        frames = self._pay.fund(app, model)
        root_seq = node.account_seq(app, self.root.raw) + 1
        body = _OperationBody(
            OperationType.INVOKE_HOST_FUNCTION,
            cx.InvokeHostFunctionOp(hostFunction=cx.HostFunction(
                cx.HostFunctionType.HOST_FUNCTION_TYPE_CREATE_CONTRACT,
                cx.CreateContractArgs(
                    contractIDPreimage=self._preimage,
                    executable=cx.ContractExecutable(
                        cx.ContractExecutableType
                        .CONTRACT_EXECUTABLE_STELLAR_ASSET))), auth=[]))
        deploy = _signed(self.network_id, self.root, root_seq, body,
                         _soroban_ext([], [instance_key(self.contract)]))
        submit(app, [deploy])
        app.manual_close()
        return frames + [deploy]

    # ----------------------------------------------------- transfers --
    def _kinds(self, n: int) -> list:
        """The fault of each of a ledger's `n` transfers, by position in
        the ledger's cycle: the relayed ones first, the adversarial
        among them drawn from the seed."""
        kinds = [None] * n
        faulty = self._rng.sample(range(self.relayed),
                                  self.adversarial * len(KINDS))
        for k, at in enumerate(faulty):
            kinds[at] = KINDS[k % len(KINDS)]
        return kinds

    def next_ledger(self, ledger_seq: int) -> list:
        """[(frame, Transfer, kind)] of the ledger that will close as
        `ledger_seq`: a seeded cycle through all accounts."""
        n = len(self.accounts)
        order = list(range(n))
        self._rng.shuffle(order)
        kinds = self._kinds(n)
        made = []                  # this ledger's sound (address, nonce)
        out = [None] * n
        # the reuses last: they take a pair some sound transfer has used
        for i in sorted(range(n), key=lambda i: kinds[i] == "nonce_reuse"):
            src = self.accounts[order[i]]
            relayed = i < self.relayed
            frm = self.accounts[order[(i + 1) % n]] if relayed else src
            dst = self.accounts[order[(i + (2 if relayed else 1)) % n]]
            amount = self._rng.choice(self.amounts)
            nonce = self._rng.getrandbits(63) if relayed else None
            if kinds[i] == "nonce_reuse":
                # of an earlier ledger (of this one, in the first); the
                # pair's owner is the `from` of this transfer
                pool = self.consumed or made
                raw, nonce = pool[self._rng.randrange(len(pool))]
                frm = self._index[raw]
            src.seq += 1
            out[i] = self._transfer(ledger_seq, src, frm, dst, amount,
                                    nonce, kinds[i])
            if relayed and kinds[i] is None:
                made.append((frm.raw, nonce))
        self.consumed.extend(made)
        self.ledgers_made += 1
        return out

    def _transfer(self, ledger_seq, src, frm, dst, amount, nonce, kind):
        """One transfer; relayed where `nonce` is given."""
        invoke = cx.InvokeContractArgs(
            contractAddress=self.contract, functionName=b"transfer",
            args=[cx.SCVal(cx.SCValType.SCV_ADDRESS,
                           _account_address(frm.raw)),
                  cx.SCVal(cx.SCValType.SCV_ADDRESS,
                           _account_address(dst.raw)),
                  _i128(amount)])
        invocation = cx.SorobanAuthorizedInvocation(
            function=cx.SorobanAuthorizedFunction(
                cx.SorobanAuthorizedFunctionType
                .SOROBAN_AUTHORIZED_FUNCTION_TYPE_CONTRACT_FN, invoke),
            subInvocations=[])
        rw = [LedgerKey.account(PublicKey.ed25519(frm.raw)),
              LedgerKey.account(PublicKey.ed25519(dst.raw))]
        expiration = signer = signature = None
        if nonce is not None:
            expiration = ledger_seq - 1 if kind == "expired" \
                else ledger_seq + self.expiration_ahead
            key = self.stranger if kind == "wrong_signer" else frm
            signature = key.key.sign(soroban_auth_payload(
                self.network_id, nonce, expiration, invocation))
            if kind == "bad_signature":
                signature = bytes([signature[0] ^ 1]) + signature[1:]
            signer = key.raw
            sym = cx.SCValType.SCV_SYMBOL
            credentials = cx.SorobanCredentials(
                cx.SorobanCredentialsType.SOROBAN_CREDENTIALS_ADDRESS,
                cx.SorobanAddressCredentials(
                    address=_account_address(frm.raw), nonce=nonce,
                    signatureExpirationLedger=expiration,
                    signature=cx.SCVal(cx.SCValType.SCV_VEC, [cx.SCVal(
                        cx.SCValType.SCV_MAP, [
                            cx.SCMapEntry(
                                key=cx.SCVal(sym, b"public_key"),
                                val=cx.SCVal(cx.SCValType.SCV_BYTES,
                                             signer)),
                            cx.SCMapEntry(
                                key=cx.SCVal(sym, b"signature"),
                                val=cx.SCVal(cx.SCValType.SCV_BYTES,
                                             signature))])])))
            rw.append(nonce_key(frm.raw, nonce))
        else:
            credentials = cx.SorobanCredentials(
                cx.SorobanCredentialsType
                .SOROBAN_CREDENTIALS_SOURCE_ACCOUNT)
        body = _OperationBody(
            OperationType.INVOKE_HOST_FUNCTION,
            cx.InvokeHostFunctionOp(
                hostFunction=cx.HostFunction(
                    cx.HostFunctionType.HOST_FUNCTION_TYPE_INVOKE_CONTRACT,
                    invoke),
                auth=[cx.SorobanAuthorizationEntry(
                    credentials=credentials, rootInvocation=invocation)]))
        ro = [instance_key(self.contract)]
        frame = _signed(self.network_id, src, src.seq, body,
                        _soroban_ext(ro, rw))
        transfer = Transfer(
            source=src.raw, frm=frm.raw, to=dst.raw, amount=amount,
            credential="source" if nonce is None else "address",
            nonce=nonce, expiration=expiration, signer=signer,
            signature=signature, inclusion_fee=INCLUSION_FEE,
            resource_fee=RESOURCE_FEE,
            resources=(INSTRUCTIONS, READ_BYTES, WRITE_BYTES, len(ro),
                       len(rw)),
            envelope_size=len(frame.envelope_bytes()))
        return frame, transfer, kind
