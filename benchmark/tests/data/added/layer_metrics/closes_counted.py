"""A per-layer metric added as a file only: closes the window counted."""


def read(cell):
    return cell.traffic_counts.get("ledgers")
