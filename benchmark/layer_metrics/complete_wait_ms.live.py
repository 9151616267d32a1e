"""What a live close stands still for the previous ledger's tail (ms),
with the client's submission of the next ledger between the two.

The reading is `complete_wait_ms.catchup`'s, made by that reader, in the
cell `standalone-pay1000.closed`. A program whose manual close joins its
own tail before it returns reads ~0 here (the queue is empty at every
seal, and the wait is in `herder.joinCompletion`); one that returns at
the commit reads what of the tail outlasted the next ledger's admission
and apply."""


def read(cell):
    return cell.spec.layer_reader("complete_wait_ms.catchup")(cell)
