"""Plain reference for threshold (m-of-n) authorisation: from an
account's signers, weights and thresholds, an envelope's decorated
signatures and the pure-Python oracle of this directory, decide whether
the envelope is authorised and whether one of its signatures is unused.

Imports nothing of the program. The semantics are the protocol's
(Stellar-ledger-entries.x `AccountEntry.thresholds` and `signers`,
CAP-0015 for fee bumps), as the sequential `SignatureChecker` with the
native verifier decides them:

- a decorated signature is tried against a signer only where its 4-byte
  hint equals the last four bytes of the signer's key; it counts where
  the oracle verifies it over the envelope's hash;
- within one check a signer counts once, weights are capped at 255 and
  sum until they reach the needed threshold; a check with no matching
  signature fails even at threshold 0;
- a transaction's source is checked at its low threshold, then every
  operation's source at the operation's level (a payment: medium; a
  SetOptions that touches signers or thresholds: high); a signature
  that verified in an earlier check may be matched again in a later
  one;
- a signature that no check matched makes the envelope txBAD_AUTH_EXTRA;
- a fee bump's outer signatures are checked against the fee source at
  its low threshold over the outer hash (and must all be used), then the
  inner envelope as above over the inner hash.

Only ed25519 signers exist in this deployment, so only they are
modelled. Codes are the names of the XDR enums, as strings.
"""

from benchmark.reference import ed25519_oracle as oracle

LOW, MEDIUM, HIGH = 0, 1, 2


class Account:
    """One account's signing state: the master key's weight, the other
    signers as [(key, weight)] in ledger order (sorted by key, as
    `AccountEntry.signers` is kept), thresholds (low, medium, high)."""

    def __init__(self, key: bytes, master_weight: int = 1, signers=(),
                 thresholds=(0, 0, 0)):
        self.key = key
        self.master_weight = master_weight
        self.signers = sorted(signers)
        self.thresholds = tuple(thresholds)

    def signers_with_master(self) -> list:
        out = [(self.key, self.master_weight)] if self.master_weight else []
        return out + list(self.signers)

    def set_signer(self, key: bytes, weight: int) -> None:
        """SetOptions' signer field: weight 0 removes, else adds or
        replaces."""
        rest = [(k, w) for k, w in self.signers if k != key]
        if weight:
            rest.append((key, weight))
        self.signers = sorted(rest)


class Checker:
    """The checks of one envelope part (one hash, one list of decorated
    signatures [(hint, signature)])."""

    def __init__(self, msg: bytes, signatures, verify=oracle.verify):
        self.msg = msg
        self.signatures = list(signatures)
        self.used = [False] * len(self.signatures)
        self._verify = verify

    def check(self, signers, needed: int) -> bool:
        left = [(k, min(w, 255)) for k, w in signers]
        total = 0
        for i, (hint, sig) in enumerate(self.signatures):
            for j, (key, weight) in enumerate(left):
                if hint == key[-4:] and self._verify(key, sig, self.msg):
                    self.used[i] = True
                    total += weight
                    if total >= needed:
                        return True
                    left.pop(j)
                    break
        return False

    def all_used(self) -> bool:
        return all(self.used)


def _inner_verdict(accounts: dict, part: dict, verify) -> str:
    """`part`: {"hash", "signatures": [(hint, sig)], "source": key,
    "ops": [(source key or None, level)]}."""
    checker = Checker(part["hash"], part["signatures"], verify)
    source = accounts[part["source"]]
    if not checker.check(source.signers_with_master(),
                         source.thresholds[LOW]):
        return "txBAD_AUTH"
    failed = False
    for op_source, level in part["ops"]:
        acct = accounts[op_source or part["source"]]
        if not checker.check(acct.signers_with_master(),
                             acct.thresholds[level]):
            failed = True
    if failed:
        return "txFAILED"          # its operations carry opBAD_AUTH
    if not checker.all_used():
        return "txBAD_AUTH_EXTRA"
    return "txSUCCESS"


def envelope_verdict(accounts: dict, envelope: dict,
                     verify=oracle.verify) -> tuple:
    """(authorised, result code, inner result code or None) of
    `envelope`: an inner part as `_inner_verdict` takes it, and for a
    fee bump beside it "outer": {"hash", "signatures", "fee_source"}.
    `accounts`: {key: Account}. What `check_valid` answers for an
    envelope whose sequence number, fee and balances are in order."""
    outer = envelope.get("outer")
    if outer is None:
        code = _inner_verdict(accounts, envelope, verify)
        return code == "txSUCCESS", code, None
    checker = Checker(outer["hash"], outer["signatures"], verify)
    payer = accounts[outer["fee_source"]]
    if not checker.check(payer.signers_with_master(),
                         payer.thresholds[LOW]):
        return False, "txBAD_AUTH", None
    if not checker.all_used():
        return False, "txBAD_AUTH_EXTRA", None
    inner = _inner_verdict(accounts, envelope, verify)
    if inner != "txSUCCESS":
        return False, "txFEE_BUMP_INNER_FAILED", inner
    return True, "txFEE_BUMP_INNER_SUCCESS", "txSUCCESS"


def candidate_tuples(accounts: dict, envelope: dict) -> set:
    """Every (key, signature, hash) the checks above can ask a verifier
    for: what a resolver has to have made for this envelope to be
    answered from a table."""
    out = set()

    def part(hash_, signatures, keys):
        for hint, sig in signatures:
            for key in keys:
                if hint == key[-4:]:
                    out.add((key, sig, hash_))

    def keys_of(acct_key):
        return [k for k, _ in accounts[acct_key].signers_with_master()]

    outer = envelope.get("outer")
    if outer is not None:
        part(outer["hash"], outer["signatures"],
             keys_of(outer["fee_source"]))
    named = [envelope["source"]] + [s for s, _ in envelope["ops"] if s]
    for acct in named:
        part(envelope["hash"], envelope["signatures"], keys_of(acct))
    return out
