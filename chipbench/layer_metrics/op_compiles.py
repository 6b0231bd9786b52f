"""Operator build: programs a window task compiled rather than loaded from
the persistent compile cache, per task, from the ``compiles`` count its
terminal trace event carries (``repro.obs.device``)."""


def read(run):
    counts = [e.data["compiles"] for e in run.events
              if e.kind in ("done", "fail") and "compiles" in e.data]
    return sum(counts) / len(counts) if counts else None
