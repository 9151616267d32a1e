"""The dictionary model's lines for the two transactions the
signature-dense traffic adds to payments: a fee bump and a SetOptions.

Imports nothing of the program. The semantics are the protocol's:

- CAP-0015: the fee source of a fee-bump envelope pays the fee, the
  inner transaction's source pays nothing but its payment, and its
  sequence number goes up by one. The bump counts as one operation
  more, so outside surge pricing the fee charged is the base fee times
  (inner operations + 1); the fee source's own sequence number stays.
- a SetOptions transaction moves no balance but its fee, the base fee
  times its operations (one a signer added or removed), and takes one
  sequence number.
"""

from benchmark.reference.ledger_model import BASE_FEE, LedgerModel


class MultisigLedgerModel(LedgerModel):
    def fee_bump_pay(self, sponsor: bytes, src: bytes, dst: bytes,
                     amount: int) -> None:
        self.balance[sponsor] -= 2 * BASE_FEE
        self.balance[src] -= amount
        self.balance[dst] += amount
        self.seq[src] += 1
        self.applied += 1

    def set_options(self, src: bytes, operations: int) -> None:
        self.balance[src] -= operations * BASE_FEE
        self.seq[src] += 1
        self.applied += 1
