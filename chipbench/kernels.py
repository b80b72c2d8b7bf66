"""Shared arithmetic of the kernel roofline readers: find a kernel's events
in the reduced trace by the names its reader lists, read the operand shapes
from each event's name (on a TPU, the whole HLO instruction), and set the
least time of that work against the time the events took."""
from __future__ import annotations

import re

_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "s64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)"
                    r"\[([0-9,]*)\]")


def nbytes(shape) -> int:
    dtype, dims = shape
    size = _ITEMSIZE[dtype]
    for d in dims:
        size *= d
    return size


def shapes_of(text: str) -> list:
    """Every (dtype, dims) of an HLO instruction's text, in order: the
    output first, then the operands; attributes after the operand list
    (layout constraints repeat the shapes) are left out."""
    head = re.split(r"\), [a-z_]+=", text, maxsplit=1)[0]
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(head)]


def roofline_share(ctx, names, work):
    """Percent of the roofline that the events whose HLO instruction name
    starts with one of ``names`` reach, or None when the trace holds none
    of them (an instruction that takes the kernel's output as an operand
    names it too, but not first)."""
    if ctx.trace is None:
        return None
    seconds = least = 0.0
    for name, op in ctx.trace.ops.items():
        if not name.startswith(tuple(names)):
            continue
        try:
            flops, nbytes_ = work(shapes_of(name))
        except (ValueError, IndexError):     # no operand shapes in the name
            return None
        per_call = max(flops / ctx.peak["bf16_flops_per_s"],
                       nbytes_ / ctx.peak["hbm_bytes_per_s"])
        least += per_call * op.count / ctx.trace.n_devices
        seconds += op.seconds
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
