"""Crypto layer: share of the verify service's flushes that were under
`VERIFY_DEVICE_MIN_BATCH` and ran per signature on the host (%):
`crypto.verify_service.flush.native` over the flushes
(`crypto.verify_service.occupancy`'s count). In this cell they are the
SCP envelopes' own signatures, a flush of one each; `correct` holds
that no burst is among them. Nothing on a program without the counter;
0.0 where nothing was flushed."""


def read(cell):
    if "crypto.verify_service.flush.native" not in cell.counters:
        return None
    native, _ = cell.counters["crypto.verify_service.flush.native"]
    flushes, _ = cell.counters.get("crypto.verify_service.occupancy",
                                   (0, 0.0))
    return 100.0 * native / flushes if flushes else 0.0
