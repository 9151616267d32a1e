"""Ledger layer, the close's reads for contract transactions: lookups of
the ledger root that neither its cache nor the close's prefetch
answered and that went to a point SELECT of the SQL store (a lookup
the bucket list answers is not counted), for one `invoke_host_function`:
`ledger.root.point.sql` over the `soroban.invoke` zone's count. 0 where
every key apply touches rode the close's one prefetch (a footprint's
TTL keys and the CONFIG_SETTING keys with it). Nothing on a program
without the counter; 0.0 where nothing was invoked."""


def read(cell):
    if "ledger.root.point.sql" not in cell.counters:
        return None
    reads, _ = cell.counters["ledger.root.point.sql"]
    invokes, _ = cell.zones.get("soroban.invoke", (0, 0.0))
    return reads / invokes if invokes else 0.0
