"""Crypto layer: tuples a flush of the verify service, mean: the
program's `crypto.verify_service.occupancy` histogram, sum over count.
A burst is one flush of 200; every SCP envelope's own signature is a
flush of one (`verify_service_native_share.flood` is their share), so
the mean lies between. Nothing in a window without a flush."""


def read(cell):
    count, total = cell.counters.get("crypto.verify_service.occupancy",
                                     (0, 0.0))
    if not count:
        return None
    return total / count
