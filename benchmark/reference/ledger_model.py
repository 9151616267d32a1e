"""Plain reference for payment traffic: a dictionary of balances and
sequence numbers, advanced by the same payments the node is given.

Imports nothing of the program. The semantics are the Stellar ones the
configurations state: a PaymentOp of the native asset moves `amount`
from source to destination, the source pays the base fee (100 stroops
for one operation when the ledger is not in surge pricing) and its
sequence number goes up by one; every acknowledged transaction is
applied exactly once.
"""

BASE_FEE = 100


class LedgerModel:
    def __init__(self):
        self.balance = {}
        self.seq = {}
        self.applied = 0

    def create(self, account: bytes, balance: int, seq: int) -> None:
        self.balance[account] = balance
        self.seq[account] = seq

    def pay(self, src: bytes, dst: bytes, amount: int) -> None:
        self.balance[src] -= amount + BASE_FEE
        self.balance[dst] += amount
        self.seq[src] += 1
        self.applied += 1

    def differences(self, observed: dict) -> int:
        """How many accounts differ from `observed`
        {account: (balance, seq)}; a missing account differs."""
        bad = 0
        for acct, bal in self.balance.items():
            if observed.get(acct) != (bal, self.seq[acct]):
                bad += 1
        return bad
