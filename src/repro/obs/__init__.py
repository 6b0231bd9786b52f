"""Flight recorder: worker-side span tracing, telemetry-carrying
heartbeats, durable JSONL trace capture with Perfetto export, and replay
loading into the virtual clock.

The subsystem spans five layers with one schema:

* ``spans``   — the per-part timing API workers instrument task execution
  with (launch_recv / deserialize / comm_build / compute / p2p_send /
  p2p_recv / spill_write / merge / jit_*), shipped back on PART_DONE and
  aligned into the parent clock via the HELLO handshake offset; thread
  tasks ship theirs on the terminal event, already in the parent clock.
* ``device``  — the JAX side: ``jit_trace`` / ``jit_lower`` /
  ``jit_compile`` spans and the ``compiles`` / ``cache_loads`` counters
  from JAX's own reports, and the thread backend's ``TaskRecorder``, whose
  spans are also ``repro/<kind>`` profiler annotations on the device
  trace's clock.
* ``metrics`` — the counter/gauge registry whose snapshot rides every
  HEARTBEAT frame (queue depth, RSS, spill bytes, peer channels,
  p2p_fallbacks), surfacing as ``telemetry`` trace events.
* ``trace``   — ``TraceWriter`` (crash-safe line-buffered JSONL via
  ``REPRO_TRACE`` / ``SchedulerSession(trace_path=)``), ``load_trace``,
  and replay through ``VirtualClockExecutor``.
* ``perfetto`` — Chrome/Perfetto ``trace.json`` export with one row per
  worker/device lane plus counter tracks
  (``python -m repro.obs.perfetto run.jsonl``).
"""
from repro.obs.metrics import MetricsRegistry, rss_mb
from repro.obs.perfetto import export_perfetto
from repro.obs.spans import (NullRecorder, SpanRecorder, align, bound,
                             current_recorder, set_current)
from repro.obs.trace import (RecordedTrace, TraceWriter, load_trace,
                             resolve_trace_path)

__all__ = [
    "MetricsRegistry", "NullRecorder", "RecordedTrace", "SpanRecorder",
    "TraceWriter", "align", "bound", "current_recorder", "export_perfetto",
    "load_trace", "resolve_trace_path", "rss_mb", "set_current",
]
