"""Crypto layer: share of the Soroban host's auth-signature checks that
the verdict table answered (%): `soroban.auth.verify.prevalidated` over
`.prevalidated` + `.fallback`. 100 where every device verdict of an
auth tuple was used; 0 on a program whose host never sees the table
(its counters, where it has them, read all `fallback`). Nothing on a
program without the counters; 0.0 where no auth signature was checked."""


def read(cell):
    if "soroban.auth.verify.prevalidated" not in cell.counters:
        return None
    hit, _ = cell.counters["soroban.auth.verify.prevalidated"]
    miss, _ = cell.counters.get("soroban.auth.verify.fallback", (0, 0.0))
    return 100.0 * hit / (hit + miss) if hit + miss else 0.0
