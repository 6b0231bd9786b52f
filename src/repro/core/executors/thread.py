"""Live in-process executor: one worker thread + private communicator per
task on real JAX devices."""
from __future__ import annotations

import dataclasses
import threading
import traceback
from time import perf_counter
from typing import Any, Optional

from repro.core.executors.base import ExecEvent, QueueEventExecutor
from repro.core.task import Task
from repro.obs import spans
from repro.obs.device import TaskRecorder


@dataclasses.dataclass
class StubComm:
    """Communicator stand-in when ``ThreadExecutor(build_comm=False)`` — used
    by tests that exercise scheduling on fake devices without JAX meshes."""
    devices: tuple
    mesh: Any = None
    build_seconds: float = 0.0
    placement: str = ""          # policy that placed the devices (pack|spread)
    p2p_bytes: int = 0           # uniform comm-stats surface: an in-process
    hub_calls: int = 0           # comm never pays a hub or peer transfer
    spills: int = 0              # nor spills shuffle partitions to disk
    raw_coll_bytes: int = 0      # nor ships raw/shm frames or forwards
    shm_bytes: int = 0           # ring blocks — constant zeros keep the
    ring_steps: int = 0          # transport counters uniform across backends
    checkpoint: Any = None       # CheckpointContext when the session runs
    # with a checkpoint root (REPRO_CKPT_DIR); None otherwise

    @property
    def size(self) -> int:
        return len(self.devices)


class ThreadExecutor(QueueEventExecutor):
    """Live executor: each task runs ``fn(comm, *args, **kwargs)`` in a
    worker thread on its allocated devices, with a freshly built private
    Communicator (the paper's per-task MPI_Comm analogue).

    Each terminal event carries the task's flight-recorder spans
    (``launch_recv``: dispatch -> the thread runs; ``comm_build``;
    ``compute``: the payload; ``jit_trace``/``jit_lower``/``jit_compile``
    while it builds programs; see :mod:`repro.obs.device`) in the
    scheduler's clock, and its ``compiles``/``cache_loads`` counts."""

    def __init__(self, build_comm: bool = True, tick: float = 0.05):
        super().__init__()
        self.build_comm = build_comm
        self.tick = tick

    def launch(self, task: Task, duration_hint: Optional[float] = None):
        def worker():
            # the task's flight recorder, bound to this thread for the whole
            # task: its own spans, the operator-build spans and counters JAX
            # reports while the payload builds, and profiler annotations
            rec = TaskRecorder(task.uid)
            rec.add("launch_recv", task.start_time, perf_counter())
            comm_s = 0.0
            ckpt = None
            if task.ckpt_dir:
                # in-process tasks always run as one part, so the p0-of-1
                # scope interoperates with single-part proc attempts
                from repro.train.checkpoint import CheckpointContext
                ckpt = CheckpointContext(task.ckpt_dir,
                                         attempt=task.ckpt_attempt or "a0")
            with spans.bound(rec):
                try:
                    if self.build_comm:
                        from repro.core.communicator import build_communicator
                        with rec.span("comm_build"):
                            comm = build_communicator(task.devices,
                                                      task.desc.mesh_axes,
                                                      task.desc.mesh_shape,
                                                      uid=f"task{task.uid}",
                                                      placement=task.placement)
                        comm_s = comm.build_seconds
                    else:
                        comm = StubComm(devices=tuple(task.devices),
                                        placement=task.placement)
                    comm.checkpoint = ckpt
                    with rec.span("compute"):
                        res = task.desc.fn(comm, *task.desc.args,
                                           **task.desc.kwargs)
                    ev = ExecEvent("done", task=task, result=res)
                except Exception as e:  # noqa: BLE001 — report any payload error
                    # keep the traceback: a device fault ends up as a fail
                    # event (and maybe a retry), which must still say where
                    # it arose
                    ev = ExecEvent(
                        "fail", task=task,
                        error=f"{type(e).__name__}: {e}\n{traceback.format_exc()}")
            ev.comm_build_s = comm_s
            ev.resumed_from_step = ckpt.resumed_from_step if ckpt else 0
            ev.compiles, ev.cache_loads = rec.compiles, rec.cache_loads
            ev.spans = spans.align(rec.export(), 0.0, worker="thread", part=0,
                                   uid=task.uid, task=task.desc.name)
            self._q.put(ev)

        threading.Thread(target=worker, daemon=True).start()
