"""Signatures that reached the device over the decorated signatures of
the checkpoints reached (%): sum of the program's
`crypto.verify.dispatch.batch` histogram (once a chunk) / decorated
signatures of every envelope, inner envelopes of fee bumps included, of
the checkpoints whose replay got as far as its batch. 100 when signer
resolution makes a tuple of every signature; a little over where two
candidate keys share a hint."""


def read(cell):
    signatures = cell.traffic_counts.get("signatures_in_checkpoints")
    if not signatures:
        return None
    _, on_device = cell.counters.get("crypto.verify.dispatch.batch",
                                     (0, 0.0))
    return 100.0 * on_device / signatures
