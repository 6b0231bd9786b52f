"""Device: share of the traced window in which no operation ran, averaged
over the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    busy, window = run.trace.busy_window()
    return 100.0 * (1.0 - busy / window) if window > 0 and busy > 0 else None
