"""Operators: least time of one operation over the device's busy time inside
the operation's span, as a mean over the window's operations.  Shared by
the ``<operator>_roofline`` readers; ``BENCHMARK.json`` names the cells
each one is read in."""


def read(run):
    if run.trace is None:
        return None
    shares = []
    for lo, hi in run.trace.spans("chipbench/op"):
        busy = run.trace.busy_mean(lo, hi)
        if busy > 0:
            shares.append(run.least_s / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
