"""The flight recorder on the device's side: operator-build spans and
counters from ``jax.monitoring``, and a per-task recorder whose spans also
mark the profiler's host plane.

JAX reports how long it spent tracing a program, lowering it and compiling
it (or loading it from the persistent compile cache) on the thread that
built it.  One module-level listener, registered when this module is first
imported, turns each report into a ``jit_trace``, ``jit_lower`` or
``jit_compile`` span of the recorder bound to that thread
(:func:`repro.obs.spans.current_recorder`), ending now and as long as JAX
says; a thread with no bound recorder drops it.  A :class:`TaskRecorder`
also counts, for its task, the programs loaded from the cache
(``cache_loads``) and the backend compiles that were not such loads
(``compiles``).

Each span a :class:`TaskRecorder` times with :meth:`TaskRecorder.span`
(``comm_build``, ``compute``) is also a
``jax.profiler.TraceAnnotation("repro/<kind>", uid=<uid>)``: when a
profiler trace is being recorded, the same span lies in its host plane, on
the clock of the device operations, with the task's ``uid`` as an event
stat.  With no trace being recorded an annotation costs a check and
nothing else.
"""
from __future__ import annotations

from contextlib import contextmanager
from time import perf_counter

import jax
from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import SpanRecorder, current_recorder

#: JAX's duration events -> the build span each one records
BUILD_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    # wraps compile_or_get_cached, so a load from the cache is one too
    "/jax/core/compile/backend_compile_duration": "jit_compile",
}
CACHE_HIT = "/jax/compilation_cache/cache_hits"
ANNOTATION_PREFIX = "repro/"


class TaskRecorder(SpanRecorder):
    """The recorder of one task: spans, a task-local counter registry, and
    a profiler annotation around each span it times."""

    __slots__ = ("uid", "metrics")

    def __init__(self, uid: int):
        super().__init__()
        self.uid = uid
        self.metrics = MetricsRegistry()

    @contextmanager
    def span(self, kind: str):
        with TraceAnnotation(ANNOTATION_PREFIX + kind, uid=self.uid):
            t0 = perf_counter()
            try:
                yield
            finally:
                self.spans.append((kind, t0, perf_counter()))

    @property
    def cache_loads(self) -> int:
        return self.metrics.get("cache_loads")

    @property
    def compiles(self) -> int:
        """Backend compiles of the task that were not loads from the
        persistent cache."""
        return self.metrics.get("backend_compiles") - self.cache_loads


def _on_duration(event: str, seconds: float, **_):
    kind = BUILD_SPANS.get(event)
    if kind is None:
        return
    t1 = perf_counter()
    rec = current_recorder()
    rec.add(kind, t1 - seconds, t1)
    if kind == "jit_compile" and isinstance(rec, TaskRecorder):
        rec.metrics.inc("backend_compiles")


def _on_event(event: str, **_):
    # a cache hit is reported inside its backend compile, on the same thread
    if event == CACHE_HIT:
        rec = current_recorder()
        if isinstance(rec, TaskRecorder):
            rec.metrics.inc("cache_loads")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
