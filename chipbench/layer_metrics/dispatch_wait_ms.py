"""Pilot scheduler: mean wait of a window task from its submit to its
dispatch onto the chips, from the session's trace events."""


def read(run):
    submits = {e.uid: e.t for e in run.events if e.kind == "submit"}
    waits = [e.t - submits[e.uid] for e in run.events
             if e.kind == "dispatch" and e.uid in submits]
    return 1e3 * sum(waits) / len(waits) if waits else None
