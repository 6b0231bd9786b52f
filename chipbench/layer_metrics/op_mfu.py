"""Whole operation: least time of one operation over its wall time on the
host clock, from submit to the end of the task, over the window."""


def read(run):
    ok = [o for o in run.ops if o.ok]
    if not ok:
        return None
    wall = sum(o.t1 - o.t0 for o in ok) / len(ok)
    return 100.0 * run.least_s / wall
