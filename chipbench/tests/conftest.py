"""Helpers for the benchmark's own tests, which run on the CPU:

    python -m pytest chipbench/tests

``tiny_root`` copies the benchmark into a temporary checkout whose
``BENCHMARK.json`` holds small cells of the same traffic drivers, so a test
can drive a whole run without a chip."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_TRAFFIC = {
    "tiny-er": {"driver": "solve", "graph": "er_dense", "n": 300,
                "rho": 0.15, "problem": "mvc", "max_d": 8, "max_evals": 8},
    "tiny-ba": {"driver": "solve", "graph": "ba_csr", "n": 2000, "d": 4,
                "problem": "mvc", "max_d": 125, "max_evals": None},
}
TINY_CELLS = {"tiny-dense": ("s2v-dense", "tiny-er"),
              "tiny-csr": ("s2v-csr", "tiny-ba")}
TINY_LIMITS = {"pick_gap": 1e-5, "mismatch": 0}


def make_root(tmp: pathlib.Path, cells=TINY_CELLS) -> pathlib.Path:
    """A checkout in ``tmp`` holding the benchmark's files and a
    BENCHMARK.json that lists ``cells`` (name -> (config, traffic))."""
    root = tmp / "chipbench"
    shutil.copytree(REPO / "chipbench", root, ignore=shutil.ignore_patterns(
        ".jax_cache", ".traces", "tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = list(cells)
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "small cell for the CPU tests"}
                          for n, (c, t) in cells.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-dense"] if m["name"].startswith("dense")
                              else names)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    for name, params in TINY_TRAFFIC.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(params))
    for name in cells:
        (root / "workloads" / f"{name}.json").write_text(
            json.dumps({"limits": TINY_LIMITS}))
    peaks = json.loads((root / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (root / "peaks.json").write_text(json.dumps(peaks))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_cell(root, cell, *, seed=7, seconds=0.5, trace=0):
    import jax
    from chipbench import run
    jax.config.update("jax_enable_compilation_cache", False)
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    return run.run(args, root=root, devices=jax.devices())
