"""Herder layer: `recv_tx_set` of a hash to the first verdict on it (ms):
mean of the program's `herder.txset.receivedToValidated` timer. In this
traffic the first envelope that names the set follows it at once, so it
reads the validation and what SCP does before it asks. Nothing on a
program without the timer; 0 where it has no sample."""


def read(cell):
    if "herder.txset.receivedToValidated" not in cell.counters:
        return None
    n, seconds = cell.counters["herder.txset.receivedToValidated"]
    return seconds / n * 1e3 if n else 0.0
