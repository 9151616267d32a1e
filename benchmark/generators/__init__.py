"""Traffic drivers. `traffic/<mix>.json` names one of these files as its
`generator` and gives it `params`; a new mix for a driver that exists is
a data file only. A driver file has a class `Driver(cell)` with

    setup()          build nodes and inputs from cell.seed, warm up every
                     device shape the window or the checks will use
    window(seconds)  drive the timed path; set t_start, t_end (on
                     time.perf_counter), attempted, failed; add the
                     window's counters, zones and traffic counts to cell
    after_window()   anything that belongs in the traced window but not
                     in the measured one
    check()          [harness.checks.Check]: each number beside its limit
    end_to_end()     {metric name: value} of this window
    close()          stop every node

`payments.py` is the seeded payment traffic both drivers share.
"""
