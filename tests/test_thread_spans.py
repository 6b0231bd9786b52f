"""The thread backend's flight recorder: each task's spans (dispatch to the
thread, communicator build, payload, and the operator builds JAX reports),
its compile and cache-load counts, its profiler annotations on the device
trace's clock, and the named phases of the distributed operators."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
BUILD_KINDS = {"jit_trace", "jit_lower", "jit_compile"}
PHASES = {"pack", "exchange", "splitters", "argsort", "permute", "search",
          "gather"}

TWO_TASKS = r"""
import json, sys
import jax
import numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.core import (PilotDescription, PilotManager, SchedulerSession,
                        TaskDescription, ThreadExecutor)
from repro.dataframe import ops_dist as D


def payload(comm, data):
    table = D.shard_table(comm, data, 64)
    out, _ = D.make_dist_sort(comm.mesh, "k")(table)   # a new jit each task
    return int(np.asarray(out.nrows).sum())


rm = PilotManager().submit_pilot(PilotDescription(n_devices=2)).resource_manager
session = SchedulerSession(ThreadExecutor(), rm, result_cache="0", ckpt_root="")
data = {"k": np.arange(100, dtype=np.int32)[::-1].copy(),
        "v": np.ones(100, np.float32)}
for _ in range(2):
    session.submit([TaskDescription(name="sort", ranks=2, fn=payload,
                                    args=(data,), max_retries=0)])
    assert session.wait_any(timeout=120)
report = session.close()
print(json.dumps({"spans": report.spans,
                  "events": [e.asdict() for e in report.trace]}))
"""


def _by_kind(spans, uid):
    out = {}
    for s in spans:
        if s["uid"] == uid:
            out.setdefault(s["kind"], []).append(s)
    return out


def test_thread_tasks_ship_their_spans_and_build_counts(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", TWO_TASKS, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    spans, events = got["spans"], got["events"]
    done = [e for e in events if e["kind"] == "done"]
    assert len(done) == 2
    for e in done:
        uid = e["uid"]
        kinds = _by_kind(spans, uid)
        assert {"launch_recv", "comm_build", "compute"} | BUILD_KINDS <= set(kinds)
        assert all(s["worker"] == "thread" and s["part"] == 0
                   and s["task"] == "sort"
                   for ss in kinds.values() for s in ss)
        (launch,), (build,), (compute,) = (kinds["launch_recv"],
                                           kinds["comm_build"],
                                           kinds["compute"])
        dispatch = next(d["t"] for d in events
                        if d["kind"] == "dispatch" and d["uid"] == uid)
        # dispatch, the thread, the communicator, the payload, done: in order
        assert abs(launch["t0"] - dispatch) < 1e-3
        assert launch["t1"] <= build["t0"] <= build["t1"] <= compute["t0"]
        assert compute["t1"] <= e["t"]
        for kind in BUILD_KINDS:
            for s in kinds[kind]:
                assert compute["t0"] - 1e-3 <= s["t0"] <= s["t1"] <= compute["t1"] + 1e-3
    first, second = (e["data"] for e in done)
    assert first["compiles"] >= 1 and first["cache_loads"] == 0
    # the same operator rebuilt by the next task comes from the cache
    assert second["compiles"] == 0 and second["cache_loads"] >= 1


def test_failing_payload_still_ships_its_spans():
    import jax
    import jax.numpy as jnp
    from repro.core import ResourceManager, SchedulerSession, ThreadExecutor
    from repro.core.task import TaskDescription

    def payload(comm):
        jax.jit(lambda x: jnp.cumsum(x) * 3)(jnp.arange(7.0)).block_until_ready()
        raise ValueError("payload fault")

    session = SchedulerSession(ThreadExecutor(build_comm=False),
                               ResourceManager(["d0"]), result_cache="0",
                               ckpt_root="")
    (task,) = session.submit([TaskDescription(name="bad", ranks=1, fn=payload,
                                              max_retries=0)])
    assert session.wait_any(timeout=60)
    report = session.close()
    assert "payload fault" in task.error
    kinds = _by_kind(report.spans, task.uid)
    assert {"launch_recv", "compute", "jit_trace"} <= set(kinds)
    (fail,) = [e for e in report.trace if e.kind == "fail"]
    assert {"compiles", "cache_loads", "hub_calls"} <= set(fail.data)
    assert kinds["compute"][0]["t1"] <= fail.t


def test_concurrent_tasks_keep_their_own_build_spans():
    """Eight tasks build programs at once on eight threads: each task's
    build reports land in its own recorder, none in another's."""
    import jax
    import jax.numpy as jnp
    from repro.core import ResourceManager, SchedulerSession, ThreadExecutor
    from repro.core.task import TaskDescription

    def payload(comm, i):
        x = jnp.arange(64.0 + i)                 # a distinct program a task
        for _ in range(3):
            jax.jit(lambda v: jnp.sort(v) * i)(x).block_until_ready()
        return i

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        session = SchedulerSession(ThreadExecutor(build_comm=False),
                                   ResourceManager([f"d{i}" for i in range(8)]),
                                   result_cache="0", ckpt_root="")
        tasks = session.submit([TaskDescription(name=f"t{i}", ranks=1,
                                                fn=payload, args=(i,))
                                for i in range(8)])
        while session.wait_any(timeout=120):
            pass
        report = session.close()
    finally:
        sys.setswitchinterval(switch)
    assert all(t.result == i for i, t in enumerate(tasks))
    for t in tasks:
        kinds = _by_kind(report.spans, t.uid)
        (compute,) = kinds["compute"]
        assert len(kinds["jit_lower"]) >= 3
        for kind in BUILD_KINDS:
            for s in kinds[kind]:
                assert compute["t0"] - 1e-3 <= s["t0"] <= s["t1"] <= compute["t1"] + 1e-3
        done = next(e for e in report.trace
                    if e.kind == "done" and e.uid == t.uid)
        assert done.data["compiles"] + done.data["cache_loads"] == \
            len(kinds["jit_compile"])


def test_compute_annotation_lies_on_the_device_trace_clock(tmp_path):
    """A profile recorded here holds ``repro/compute`` with the task's uid;
    the offset of its start from the ``compute`` span's start maps the
    span's end onto the annotation's end."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro.core import ResourceManager, SchedulerSession, ThreadExecutor
    from repro.core.task import TaskDescription

    def payload(comm):
        return float(jnp.sort(jnp.arange(50_000.0)[::-1]).sum())

    session = SchedulerSession(ThreadExecutor(), ResourceManager(jax.devices()[:1]),
                               result_cache="0", ckpt_root="")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tasks = session.submit([TaskDescription(name="t", ranks=1, fn=payload)
                                for _ in range(2)])
        while session.wait_any(timeout=60):
            pass
    finally:
        jax.profiler.stop_trace()
    report = session.close()
    (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
    profile = ProfileData.from_file(str(path))
    found = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro/"):
                        found.setdefault((e.name, dict(e.stats).get("uid")),
                                         []).append(e)
    for task in tasks:
        assert ("repro/comm_build", task.uid) in found
        (ann,) = found[("repro/compute", task.uid)]
        (span,) = _by_kind(report.spans, task.uid)["compute"]
        offset = ann.start_ns * 1e-9 - span["t0"]
        end = (ann.start_ns + ann.duration_ns) * 1e-9
        assert abs(span["t1"] + offset - end) < 1e-3


SCOPES = r"""
import json, re
import jax
import numpy as np
from repro.core import build_communicator
from repro.dataframe import ops_dist as D

comm = build_communicator(jax.devices(), axes=("df",))
t = D.shard_table(comm, {"k": np.arange(64, dtype=np.int32),
                         "v": np.ones(64, np.float32)}, 80)
out = {}
for name, fn, args in [("join", D.make_dist_join(comm.mesh, "k"), (t, t)),
                       ("sort", D.make_dist_sort(comm.mesh, "k"), (t,))]:
    text = fn.lower(*args).as_text(debug_info=True)
    out[name] = sorted({part for loc in re.findall(r'loc\("([^"]*)"', text)
                        for part in loc.split("/")})
print(json.dumps(out))
"""


def test_lowered_operators_carry_their_phase_scopes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", SCOPES], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert PHASES - {"splitters"} <= set(got["join"])
    assert PHASES - {"search", "gather"} <= set(got["sort"])


@pytest.mark.parametrize("op", ["ops_dist", "ops_local", "comm"])
def test_scopes_are_named_in_the_source(op):
    """Each scope name in the operators' source is one of the seven phases
    the benchmark's readers know."""
    text = (ROOT / "src" / "repro" / "dataframe" / f"{op}.py").read_text()
    names = set(re.findall(r'named_scope\("([a-z_]+)"\)', text))
    assert names and names <= PHASES
