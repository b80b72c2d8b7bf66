"""Device time per program scope, joined from one profiler trace
(``.xplane.pb``) to the names the program gives its layers.

The program wraps the layers of its solve path in ``jax.named_scope``s
(``s2v.embed``, ``q.head``, ``env.select``, ``env.commit``).  XLA keeps
the scope path in each HLO instruction's ``op_name`` metadata, and the TPU
profiler stores every module's optimised HLO in the ``/host:metadata``
plane, as the ``Hlo Proto`` stat of an event-metadata entry named like the
module's events on the device's ``XLA Modules`` line (``jit_solve_fn(<id>)``).
The join:

- builds each module's table of instruction name -> ``op_name`` from that
  embedded HLO (read here with a small protobuf wire-format reader; no
  generated protobuf classes are needed); an instruction that the compiler
  made without an ``op_name`` takes the one of the computation it calls;
- assigns each ``XLA Ops`` event to the ``XLA Modules`` event that contains
  it, so equal instruction names of different modules do not clash;
- takes each op event's self time (``trace.self_times``) within the
  traced window ``bench.window``, and sums it per scope (a scope counts
  when it is a whole ``/``-separated component of the ``op_name``), per
  module, and under no scope;
- labels each idle gap of the device with the innermost host span whose
  name starts with ``solve.`` that covers the gap's middle, else with the
  innermost ``bench.`` span, as ``trace.reduce`` does.

XLA renumbers fusions after any change to the program; the scope names do
not move.  A fusion carries the ``op_name`` of its root instruction, so work
that XLA fuses across two scopes is counted in one of them.

    python3 -m chipbench.scopes <trace.xplane.pb>

prints the join of one trace as JSON (``summary``).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import pathlib
import re
from typing import Optional

from chipbench import trace

SCOPES = ("s2v.embed", "q.head", "env.select", "env.commit")
PROGRAM_SPAN_PREFIX = "solve."
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"
SOLVE_MODULE = "jit_solve_fn"

_INSTRUCTION_NAME = re.compile(r"^%([\w.\-]+) = ")


# -- protobuf wire format ------------------------------------------------------

def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized message:
    an int for a varint, a memoryview for a length-delimited field; fixed
    32- and 64-bit fields are skipped."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _first(buf, number: int):
    for field, value in _fields(buf):
        if field == number:
            return value
    return None


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace") if value is not None \
        else ""


# XSpace.planes=1; XPlane.name=2, event_metadata=4, stat_metadata=5 (map
# entries key=1, value=2); XEventMetadata.name=2, stats=5; XStatMetadata
# .name=2; XStat.metadata_id=1, bytes_value=6.  HloProto.hlo_module=1;
# HloModuleProto.computations=3; HloComputationProto.instructions=2, id=5;
# HloInstructionProto.name=1, metadata=7, called_computation_ids=38
# (packed); OpMetadata.op_name=2.

def _map_values(plane, number: int) -> dict:
    out = {}
    for field, entry in _fields(plane):
        if field == number:
            key = value = None
            for f, v in _fields(entry):
                if f == 1:
                    key = v
                elif f == 2:
                    value = v
            out[key] = value
    return out


def _packed(value) -> list:
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _op_names(hlo_proto) -> dict:
    """Instruction name -> op_name of every instruction of one module.

    Some compiler passes make an instruction without metadata (on the TPU,
    the fusions around a sorted scatter-add).  Such an instruction takes
    the op_name of what it calls: the first op_name found walking each
    called computation back from its root."""
    module = _first(hlo_proto, 1)
    if module is None:
        return {}
    computations = {}          # id -> [(name, op_name, called ids)]
    for field, computation in _fields(module):
        if field != 3:
            continue
        rows = []
        for f, instruction in _fields(computation):
            if f != 2:
                continue
            name, op_name, called = None, "", []
            for g, v in _fields(instruction):
                if g == 1:
                    name = _text(v)
                elif g == 7:
                    op_name = _text(_first(v, 2))
                elif g == 38:
                    called += _packed(v)
            rows.append((name, op_name, called))
        computations[_first(computation, 5)] = rows

    inner: dict = {}

    def inner_op_name(cid) -> str:
        if cid not in inner:
            inner[cid] = ""                  # guards a cycle
            for _, op_name, called in reversed(computations.get(cid, [])):
                found = op_name or next(
                    (o for o in map(inner_op_name, called) if o), "")
                if found:
                    inner[cid] = found
                    break
        return inner[cid]

    return {name: op_name or next(
                (o for o in map(inner_op_name, called) if o), "")
            for rows in computations.values()
            for name, op_name, called in rows if name is not None}


def module_tables(path: str) -> dict:
    """Module name (as on the ``XLA Modules`` line) -> {instruction name:
    op_name}, from the HLO that the trace's metadata plane embeds."""
    data = pathlib.Path(path).read_bytes()
    tables = {}
    for field, plane in _fields(data):
        if field != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_names = {k: _text(_first(v, 2))
                      for k, v in _map_values(plane, 5).items()}
        hlo_ids = {k for k, n in stat_names.items() if n == HLO_STAT}
        for meta in _map_values(plane, 4).values():
            for f, stat in _fields(meta):
                if f == 5 and _first(stat, 1) in hlo_ids:
                    proto = _first(stat, 6)
                    if proto is not None:
                        tables[_text(_first(meta, 2))] = _op_names(proto)
    return tables


# -- the join ------------------------------------------------------------------

@dataclasses.dataclass
class Scoped:
    """The join of one trace: each instruction's device self seconds in
    the traced window (averaged over devices) with its op_name, and the
    labelled idle gaps."""
    ops: dict              # (module, instruction) -> [seconds, op_name]
    gaps: list             # (label, seconds), longest first

    def seconds(self, scope: Optional[str], module: str = "") -> float:
        """Seconds under ``scope`` (None: under no scope), in the modules
        whose name starts with ``module``."""
        return sum(s for (m, _), (s, op_name) in self.ops.items()
                   if m.startswith(module) and scope_of(op_name) == scope)

    @property
    def matched(self) -> int:
        """Instructions found in their module's embedded HLO."""
        return sum(op_name is not None for _, op_name in self.ops.values())

    def coverage(self, module: str = SOLVE_MODULE) -> Optional[float]:
        """Share of ``module``'s device self time under the scopes."""
        total = sum(s for (m, _), (s, _) in self.ops.items()
                    if m.startswith(module))
        if total <= 0:
            return None
        return 1.0 - self.seconds(None, module) / total


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The scope of an ``op_name``: the innermost of SCOPES that is a
    whole path component of it, or None."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


def join(path: str, n_devices: int = 1) -> Scoped:
    """Join the trace at ``path`` to the program's scopes (see the module
    docstring)."""
    from jax.profiler import ProfileData
    tables = module_tables(path)
    devices, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (trace.OPS_LINE, MODULES_LINE)}
            if lines.get(trace.OPS_LINE):
                devices.append(lines)
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith((trace.SPAN_PREFIX,
                                                   PROGRAM_SPAN_PREFIX)))
    devices = devices[:n_devices]
    windows = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        all_ev = [(s, s + d) for dev in devices
                  for _, s, d in dev[trace.OPS_LINE]]
        lo = min((s for s, _ in all_ev), default=0.0)
        hi = max((e for _, e in all_ev), default=0.0)

    by_op: dict = {}
    gaps = []
    nested = sorted(((s, e, n) for n, s, e in spans
                     if n != trace.WINDOW_SPAN), key=lambda x: x[0])
    for dev in devices:
        mods = sorted((s, s + d, n)
                      for n, s, d in dev.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        ops = [(n, max(s, lo), min(s + d, hi))
               for n, s, d in dev[trace.OPS_LINE] if s + d > lo and s < hi]
        own = trace.self_times([(s, e - s) for _, s, e in ops])
        for (name, start, _), self_ns in zip(ops, own):
            k = bisect.bisect_right(starts, start) - 1
            module = mods[k][2] if k >= 0 and start <= mods[k][1] else ""
            m = _INSTRUCTION_NAME.match(name)
            instruction = m.group(1) if m else name
            op = by_op.setdefault((module, instruction), [
                0.0, tables.get(module, {}).get(instruction)])
            op[0] += self_ns * 1e-9 / len(devices)
        merged = trace._union([(s, e) for _, s, e in ops])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label(nested, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Scoped(ops=by_op, gaps=gaps)


def _label(nested, mid) -> str:
    """The innermost ``solve.`` span over ``mid``, else the innermost
    ``bench.`` span, else "no span"."""
    program = bench = None
    for s, e, n in nested:
        if s <= mid <= e:
            if n.startswith(PROGRAM_SPAN_PREFIX):
                program = n
            else:
                bench = n
    return program or bench or "no span"


@functools.lru_cache(maxsize=4)
def _cached_join(path: str, n_devices: int, _stamp) -> Scoped:
    return join(path, n_devices)


def cell_trace(root: pathlib.Path, cell: str) -> Optional[str]:
    """The trace that ``run.py --trace 1`` left for ``cell`` under
    ``<root>/.traces/<cell>``, or None."""
    try:
        return trace.find_xplane(pathlib.Path(root) / ".traces" / cell)
    except FileNotFoundError:
        return None


def ms_per_eval(ctx, scope: str, root: pathlib.Path) -> Optional[float]:
    """Milliseconds of device self time under ``scope`` in the traced
    window per policy evaluation of the window, or None when the trace has
    no op under the scope."""
    evals = ctx.window.counts.get("evals")
    path = cell_trace(root, ctx.cell.get("name", ""))
    if not evals or path is None or ctx.trace is None:
        return None
    st = os.stat(path)
    scoped = _cached_join(path, ctx.trace.n_devices,
                          (st.st_mtime_ns, st.st_size))
    seconds = scoped.seconds(scope)
    if seconds <= 0:
        return None
    return seconds * 1e3 / evals


def summary(path: str, top: int = 8) -> dict:
    """The join of one trace as plain data: seconds per scope and under
    none, the solve module's coverage, the idle gaps by label, and the
    largest instructions under each scope with their op_names."""
    scoped = join(path)
    gaps: dict = {}
    for label, s in scoped.gaps:
        g = gaps.setdefault(label, {"seconds": 0.0, "count": 0,
                                    "over_0.5ms": 0})
        g["seconds"] += s
        g["count"] += 1
        g["over_0.5ms"] += s >= 5e-4
    largest: dict = {}
    for (module, instruction), (s, op_name) in sorted(
            scoped.ops.items(), key=lambda kv: -kv[1][0]):
        rows = largest.setdefault(str(scope_of(op_name)), [])
        if len(rows) < top:
            rows.append([module, instruction, s, op_name])
    return {"seconds": {str(k): scoped.seconds(k)
                        for k in SCOPES + (None,)},
            "solve_coverage": scoped.coverage(),
            "instructions": len(scoped.ops), "matched": scoped.matched,
            "gaps": gaps, "largest": largest}


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(summary(sys.argv[1]), indent=1))
