"""The controls that the limits were set against, at sizes a test run can
hold: computed in a lower precision than the configuration's float32,
they must come out not correct, and the program must not.

On the chip the same readings come from ``chipbench/calibrate.py`` at
each cell's own size (``--controls``)."""
import pathlib

import jax
import pytest

from conftest import make_root

from chipbench import calibrate, harness

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _limit(cell):
    return harness.load_json(ROOT / "workloads" / f"{cell}.json")["limits"]


@pytest.mark.parametrize("tiny,cell", [("tiny-dense", "solve-dense-er21k"),
                                       ("tiny-csr", "solve-csr-ba16k")])
def test_lower_precision_fails_the_limit_and_the_program_passes(
        tmp_path, tiny, cell):
    jax.config.update("jax_enable_compilation_cache", False)
    root = make_root(tmp_path)
    limits = _limit(cell)
    rows = calibrate.main(["--workload", tiny, "--seeds", "5,6,7",
                           "--controls"], root=root, devices=jax.devices())
    for r in rows:
        assert r["pick_gap"] <= limits["pick_gap"]
        assert r["mismatch"] <= limits["mismatch"]
        # the reference in one bfloat16 pass, along the program's covers
        assert r["control_bf16"] > limits["pick_gap"]
