"""Crypto layer: per-signature verifies of transaction signatures on the
host, per transaction applied (us). The program's `crypto.verify.native`
zone counts every native verify, and a following validator makes one
for each SCP envelope it is handed (a batch of one stays on the host);
those are the traffic's `envelope_verifies`. What is read is the zone's
mean seconds a verify times the verifies beyond them: 0 where the device
batch or the verify cache answered every transaction signature, which
`correct` holds; more where a batch fell back or apply met a signature
the cache had dropped."""


def read(cell):
    txs = cell.traffic_counts.get("transactions")
    if not txs:
        return None
    count, seconds = cell.zones.get("crypto.verify.native", (0, 0.0))
    extra = count - cell.traffic_counts.get("envelope_verifies", 0)
    if not count or extra <= 0:
        return 0.0
    return seconds / count * extra / txs * 1e6
