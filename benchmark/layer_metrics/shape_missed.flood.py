"""Crypto layer: batches inside the window that met a shape nobody had
loaded and traced or compiled on the crank: the program's counter
`crypto.verify.shape.missed`. 0 on a node that loaded its live shapes
when it started, which `correct` holds (over the node's whole life).
Nothing on a program without the counter."""


def read(cell):
    if "crypto.verify.shape.missed" not in cell.counters:
        return None
    return float(cell.counters["crypto.verify.shape.missed"][0])
