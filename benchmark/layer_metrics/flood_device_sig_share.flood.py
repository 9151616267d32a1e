"""Crypto layer: share of the flooded signatures that the verify
service sent to the device (%): the program's
`crypto.verify.dispatch.batch` histogram's sum, less what the sets'
validation sent (`herder.txset.prevalidate.dispatched`), over the
frames flooded (one signature each). 100 where every burst was one
device dispatch, which `correct` holds. Nothing on a program without
the flood counters (`herder.flood.received`); 0.0 where nothing was
flooded."""


def read(cell):
    if "herder.flood.received" not in cell.counters:
        return None
    flooded = cell.traffic_counts.get("flooded")
    if not flooded:
        return 0.0
    _, on_device = cell.counters.get("crypto.verify.dispatch.batch",
                                     (0, 0.0))
    sets, _ = cell.counters.get("herder.txset.prevalidate.dispatched",
                                (0, 0.0))
    return 100.0 * (on_device - sets) / flooded
