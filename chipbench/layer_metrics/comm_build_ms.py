"""Executor: mean time to build a window task's private communicator, from
the session's ``comm_build`` trace events."""


def read(run):
    builds = [e.value for e in run.events if e.kind == "comm_build"]
    return 1e3 * sum(builds) / len(builds) if builds else None
