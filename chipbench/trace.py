"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

- device busy time: the union of the intervals in which an operation ran
  on each device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  averaged over the devices;
- device self time per operation, summed over its events and averaged over
  the devices.  On a TPU an operation's event name is its whole HLO
  instruction, shapes included (readers take a kernel's operand shapes from
  it).  Events nest: a ``while`` spans the operations of its body, so each
  event's self time leaves out the events inside it;
- the idle gaps between busy intervals inside the traced window, each
  labelled with the innermost of the benchmark's own host spans (names
  starting ``bench.``) that covers the gap's middle.

The traced window is the host span ``bench.window``; without it, the span
from the first to the last device operation.
"""
from __future__ import annotations

import dataclasses
import glob
import pathlib
import re
from typing import Optional

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Op:
    seconds: float
    count: int


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    ops: dict              # event name -> Op
    gaps: list             # (label, seconds), longest first
    n_devices: int

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)
        return {"device_ops": [[short_name(n), o.seconds]
                               for n, o in ops[:TOP]],
                "idle_gaps": [[l, s] for l, s in self.gaps[:TOP]]}


_INSTRUCTION = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.56 fusion`` for an HLO instruction's text, else the name."""
    m = _INSTRUCTION.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def self_times(events) -> list:
    """Each (start, duration) event's duration less the events that lie
    wholly inside it and inside no event nested deeper."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0],
                                                      -events[i][1]))
    own = [d for _, d in events]
    stack = []
    for i in order:
        s, d = events[i]
        while stack and s + d > events[stack[-1]][0] + events[stack[-1]][1]:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return own


@dataclasses.dataclass
class Context:
    """What a per-layer reader gets: the reduced trace, the window's
    counts, the cell, its configuration and the device's peaks."""
    trace: Optional[Reduced]
    window: object
    cell: dict
    config: dict
    peak: dict


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**" /
                                 "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the merged list, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(path: str, n_devices: int = 1) -> Reduced:
    """Reduce the trace at ``path`` (see the module docstring)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_events, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events.append([(e.name, e.start_ns, e.duration_ns)
                                          for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    device_events = [d for d in device_events if d][:n_devices] or [[]]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        all_ev = [(s, s + d) for dev in device_events for _, s, d in dev]
        lo = min((s for s, _ in all_ev), default=0.0)
        hi = max((e for _, e in all_ev), default=0.0)
    window_s = (hi - lo) * 1e-9

    ops: dict = {}
    busy = 0.0
    gaps = []
    nested = sorted(((s, e, n) for n, s, e in spans), key=lambda x: x[0])
    for dev in device_events:
        own = self_times([(s, d) for _, s, d in dev])
        for (name, _, _), self_ns in zip(dev, own):
            op = ops.setdefault(name, Op(0.0, 0))
            op.seconds += self_ns * 1e-9 / len(device_events)
            op.count += 1
        merged = _union(_clip([(s, s + d) for _, s, d in dev], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = "no span"
                for s, e, n in nested:
                    if s <= mid <= e and n != WINDOW_SPAN:
                        label = n
                gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(busy_s=busy / len(device_events), window_s=window_s,
                   ops=ops, gaps=gaps, n_devices=len(device_events))
