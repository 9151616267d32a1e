"""The `ledger.close.applyTx` zone per transaction replayed (us), with up
to twenty signers an account and one to twenty-one signatures a
transaction.

The reading is `apply_us_per_tx.catchup`'s, made by that reader, in the cell
`multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("apply_us_per_tx.catchup")(cell)
