"""Collectives: device time of the all-to-all exchanges inside each
operation's span, averaged over chips and operations.  A cell on one chip
has no exchange to read."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans("chipbench/op")
    times = [run.trace.matching_mean("all-to-all", lo, hi) for lo, hi in spans]
    if not times or not any(times):
        return None
    return 1e3 * sum(times) / len(times)
