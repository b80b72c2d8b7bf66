#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by the names in ``BENCHMARK.json``:
``configs/<config>.json`` (the policy and graph representation),
``traffic/<traffic>.json`` (the traffic driver and its parameters),
``drivers/<driver>.py`` (the code that drives that kind of traffic),
``workloads/<cell>.json`` (the limits of the correctness check) and, for
``--trace 1``, ``metrics/<metric>.py`` (one reader per per-layer metric
that ``BENCHMARK.json`` lists for the cell).

The run refuses to start without a TPU.  It makes the weights and inputs
from ``--seed``, warms up every program the window runs (``setup_s``,
counted from process start), measures for ``--seconds``, reads the peak
device memory, and then checks the window's answers against the plain
reference.  The compared numbers and their limits are printed as the last
lines of standard error and as the ``checks`` key, which comes last, of
the JSON object that is the last line of standard output.  With
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, read from the reduced trace.
"""
from __future__ import annotations

import time

_START = time.perf_counter()

import argparse           # noqa: E402
import json               # noqa: E402
import pathlib            # noqa: E402
import shutil             # noqa: E402
import sys                # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness, trace as trace_lib   # noqa: E402


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end or per_layer) that ``cell``
    reports."""
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, root: pathlib.Path = harness.ROOT,
        devices=None, start: float = _START) -> dict:
    """One run; returns the result object.  ``devices`` replaces the look
    for an accelerator (tests drive the rest of a run on the CPU)."""
    import jax
    bench, cell, config = harness.load_cell(root, args.workload)
    if devices is None:
        devices = harness.require_accelerator(cell["chips"])
    harness.use_compile_cache(root / ".jax_cache")
    compiles = harness.CompileCounter()
    driver = harness.load_module(root / "drivers" / f"{cell['driver']}.py")
    session = driver.setup(cell=cell, config=config, seed=args.seed)
    setup_s = time.perf_counter() - start

    before = compiles.count
    print(f"setup: {before} programs lowered, {setup_s:.3f} s",
          file=sys.stderr)
    trace_dir = root / ".traces" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        window = session.window(args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - before
    device = harness.device_record(devices)

    checks = session.check()
    values = dict(checks.values)
    values["compiles_in_window"] = (in_window, 0)
    correct = all(v <= limit for v, limit in values.values())

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    breakdown = None
    if args.trace:
        reduced = trace_lib.reduce(trace_lib.find_xplane(trace_dir),
                                   n_devices=len(devices))
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        peaks = harness.load_json(root / "peaks.json")
        if device["kind"] not in peaks:
            raise harness.RunError(f"no peaks for device {device['kind']!r}")
        ctx = trace_lib.Context(trace=reduced, window=window, cell=cell,
                                config=config, peak=peaks[device["kind"]])
        for m in cell_metrics(bench, args.workload, "per_layer"):
            reader = harness.load_module(root / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, args.workload, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" \
                else window.metrics[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}

    result = {"correct": correct, "attempted": window.attempted,
              "failed": checks.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, (v, limit) in values.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except harness.RunError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(f"window: {result['attempted']} calls, "
          f"{result['checks']['compiles_in_window']['value']} compiles "
          f"inside it", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
