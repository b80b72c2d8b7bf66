#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on many seeds in one process.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--controls]

For each seed it builds the cell as a run does, takes one answer from the
timed path and reads its trajectory against the reference: ``pick_gap``
and ``mismatch`` of the program (the lower readings).  With ``--controls``
it also reads the controls along the same trajectory: the reference
computed as three bfloat16 passes (``high``) and as one (``bf16``), and
the program's own ``compute="bf16"`` path, read like the program.
One JSON line per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness   # noqa: E402


def readings(session, controls: bool) -> dict:
    sol, evals = session.first
    covers = session.trajectory(evals)
    r = session.read(covers, control="high" if controls else None)
    out = {"evals": evals, "cover": int(sol.sum()), "pick_gap": r.pick_gap,
           "mismatch": r.mismatch + int((covers[-1] != sol).sum())}
    if controls:
        out["control_high"] = r.control_gap
        out["control_bf16"] = session.read(covers,
                                           control="bf16").control_gap
        rb = session.read(session.trajectory(evals, compute="bf16"))
        out["program_bf16_pick_gap"] = rb.pick_gap
        out["program_bf16_mismatch"] = rb.mismatch
    return out


def main(argv=None, *, root: pathlib.Path = harness.ROOT,
         devices=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args(argv)
    _, cell, config = harness.load_cell(root, args.workload)
    if devices is None:
        harness.require_accelerator(cell["chips"])
    harness.use_compile_cache(root / ".jax_cache")
    driver = harness.load_module(root / "drivers" / f"{cell['driver']}.py")
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        session = driver.setup(cell=cell, config=config, seed=seed)
        row = {"workload": args.workload, "seed": seed,
               **readings(session, args.controls),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del session
    return rows


if __name__ == "__main__":
    main()
