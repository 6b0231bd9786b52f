"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Host spans are the ``chipbench/...`` annotations the benchmark opens around
the window, each task and the calls inside the task body.  Device
operations are the events on the ``XLA Ops`` line of each chip's plane
(``/device:TPU:<id>``); operations in flight beside them (a collective
started and awaited later) are on its ``Async XLA Ops`` line.  Both sit on
the trace's one clock, so a device operation belongs to the task whose span
holds it, whatever the program calls its operations.  Busy time is the
union of a chip's ``XLA Ops``.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
PREFIX = "chipbench/"
TOP = 10


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return [tuple(iv) for iv in out]


def covered(merged: list, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the disjoint intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def short_name(name: str) -> str:
    """An operation's instruction name: a TPU trace names each operation by
    its whole HLO text (``%fusion.81 = s32[...] fusion(...), ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events, lo: float, hi: float) -> dict:
    """Seconds of ``[lo, hi]`` each operation ran less the time of the
    operations nested in it (a loop's body inside the loop), keyed by its
    name and, when nested, the outermost operation that holds it."""
    out: dict = {}
    stack: list = []                  # [label, end, seconds left to it]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        d = max(0.0, min(b, hi) - max(a, lo))
        if stack and b > stack[-1][1]:       # overlaps without nesting
            while stack:
                close(stack.pop())
        if stack:
            stack[-1][2] -= d
            label = f"{short_name(name)} in {stack[0][0]}"
        else:
            label = short_name(name)
        stack.append([label, b, d])
    while stack:
        close(stack.pop())
    return out


class DeviceTrace:
    def __init__(self, host_spans: dict, device_ops: dict,
                 async_ops: dict = None):
        self.host = {k: sorted(v) for k, v in host_spans.items()}
        self.ops = {c: sorted(v, key=lambda e: e[1])
                    for c, v in device_ops.items()}
        self.async_ops = async_ops or {}
        self.busy = {c: merge((a, b) for _, a, b in v)
                     for c, v in self.ops.items()}
        windows = self.host.get(PREFIX + "window", [])
        self.window = windows[0] if windows else None

    def spans(self, name: str) -> list:
        """The host spans of one annotation that lie inside the window."""
        out = self.host.get(name, [])
        if self.window is not None:
            lo, hi = self.window
            out = [s for s in out if s[0] >= lo and s[1] <= hi]
        return out

    def busy_mean(self, lo: float, hi: float) -> float:
        """Device busy seconds inside ``[lo, hi]``, averaged over chips."""
        if not self.busy:
            return 0.0
        return sum(covered(b, lo, hi) for b in self.busy.values()) / len(self.busy)

    def matching_mean(self, pattern: str, lo: float, hi: float) -> float:
        """Seconds in ``[lo, hi]`` covered by operations, in flight or
        not, whose name holds ``pattern``, averaged over chips."""
        if not self.ops:
            return 0.0
        total = 0.0
        for chip, evs in self.ops.items():
            evs = evs + self.async_ops.get(chip, [])
            total += covered(merge((a, b) for n, a, b in evs if pattern in n),
                             lo, hi)
        return total / len(self.ops)

    def busy_window(self) -> tuple:
        """``(busy seconds averaged over chips, window seconds)``."""
        if self.window is None:
            return 0.0, 0.0
        lo, hi = self.window
        return self.busy_mean(lo, hi), hi - lo

    def _innermost(self, t: float) -> str:
        best, width = "outside any span", float("inf")
        for name, spans in self.host.items():
            for lo, hi in spans:
                if lo <= t <= hi and hi - lo < width:
                    best, width = name, hi - lo
        return best

    def breakdown(self) -> dict:
        """The device operations that took most time (their own time,
        without what ran nested in them) and the idle time by the host span
        open during it, in the window, each in seconds averaged over
        chips."""
        if self.window is None or not self.ops:
            return {"device_ops": [], "idle_gaps": []}
        lo, hi = self.window
        n = len(self.ops)
        by_op: dict = {}
        for evs in self.ops.values():
            for label, d in self_times(evs, lo, hi).items():
                by_op[label] = by_op.get(label, 0.0) + d / n
        by_host: dict = {}
        for busy in self.busy.values():
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2], strict=True):
                a, b = max(a, lo), min(b, hi)
                if b > a:
                    label = self._innermost((a + b) / 2)
                    by_host[label] = by_host.get(label, 0.0) + (b - a) / n

        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:TOP]

        return {"device_ops": top(by_op), "idle_gaps": top(by_host)}


def from_profile(profile, chip_ids) -> DeviceTrace:
    """Reduce a ``jax.profiler.ProfileData``; only the chips of the cell
    count."""
    host: dict = {}
    lines = {OPS_LINE: {}, ASYNC_LINE: {}}
    chips = set(chip_ids)
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip not in chips:
                continue
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].setdefault(chip, []).extend(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    return DeviceTrace(host, lines[OPS_LINE], lines[ASYNC_LINE])


def load(trace_dir, chip_ids) -> DeviceTrace:
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(paths[-1])), chip_ids)
