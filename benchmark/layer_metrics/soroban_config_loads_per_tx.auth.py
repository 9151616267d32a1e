"""Ledger layer, the close's reads for contract transactions: network
configurations built from the ledger's CONFIG_SETTING entries, for one
`invoke_host_function`: `soroban.config.load` over the `soroban.invoke`
zone's count. Counted where a close keeps what it built: one a close
that applies a Soroban operation (0.001 at 1,000 transfers a ledger).
A program that builds one for every operation has no such counter.
Nothing on a program without the counter; 0.0 where nothing was
invoked."""


def read(cell):
    if "soroban.config.load" not in cell.counters:
        return None
    loads, _ = cell.counters["soroban.config.load"]
    invokes, _ = cell.zones.get("soroban.invoke", (0, 0.0))
    return loads / invokes if invokes else 0.0
