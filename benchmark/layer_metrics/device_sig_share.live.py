"""Share of the traffic's signatures that reached the device inside
the window: sum of the program's `crypto.verify.dispatch.batch`
histogram / signatures submitted (%)."""


def read(cell):
    signatures = cell.traffic_counts.get("signatures")
    if not signatures:
        return None
    _, on_device = cell.counters.get("crypto.verify.dispatch.batch",
                                     (0, 0.0))
    return 100.0 * on_device / signatures
