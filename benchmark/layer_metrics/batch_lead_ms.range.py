"""How long a prefetched checkpoint's verdicts were all back before its
first ledger wanted them (ms): mean of the program's
`catchup.batch.lead` timer, once a checkpoint that was dispatched while
the one before it applied, from its last chunk's landing to its first
ledger's start; 0 where apply got there first. Higher is better: it is
the budget a slower kernel or a smaller bucket may spend before the
steady checkpoint starts cold. A program without the timer reports
nothing."""


def read(cell):
    n, seconds = cell.counters.get("catchup.batch.lead", (0, 0.0))
    if not n:
        return None
    return seconds / n * 1e3
