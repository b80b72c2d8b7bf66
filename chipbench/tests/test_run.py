"""Whole runs of small cells on the CPU: the result's shape, the faults
that must turn ``correct`` false, the refusal without a chip or without the
program, and cells, configurations and metrics found by name."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, make_root, run_cell

from chipbench import harness


@pytest.mark.parametrize("cell", ["tiny-dense", "tiny-csr"])
def test_a_sound_run_is_correct_and_reports_its_metrics(tiny_root, cell):
    r = run_cell(tiny_root, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"infer_step_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["device"]["count"] == 1


def test_a_traced_run_reports_per_layer_metrics(tiny_root):
    r = run_cell(tiny_root, "tiny-csr", trace=1)
    assert r["correct"]
    # the CPU trace has no device plane: only the count-based metric reads
    assert set(r["metrics"]) == {"mfu.infer"}
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.fixture
def program(monkeypatch):
    """Reach into the program under test to plant faults; every cached
    solve program is dropped so the fault is traced in."""
    import jax
    engine = harness.import_program("repro.core.engine")
    engine._build_solve_step.cache_clear()
    yield harness.import_program
    engine._build_solve_step.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["tiny-dense", "tiny-csr"])
def test_a_step_that_leaves_the_state_unchanged_is_caught(tiny_root, program,
                                                          monkeypatch, cell):
    graphrep = program("repro.core.graphrep")
    rep = {"tiny-dense": graphrep.DenseRep, "tiny-csr": graphrep.CsrRep}[cell]
    import jax.numpy as jnp
    monkeypatch.setattr(rep, "commit", lambda self, state, sel: (
        state, jnp.zeros(state.candidate.shape[:1], bool)))
    r = run_cell(tiny_root, cell)
    assert not r["correct"]
    assert r["checks"]["mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny-dense", "tiny-csr"])
def test_an_answer_altered_where_it_is_produced_is_caught(tiny_root, program,
                                                          monkeypatch, cell):
    inference = program("repro.core.inference")
    solve = inference.solve

    def altered(*args, **kwargs):
        res = solve(*args, **kwargs)
        sol = res.solution.copy()
        sol[0, int(np.argmin(sol[0]))] = 1.0    # one node the solve left
        res.solution = sol
        return res

    monkeypatch.setattr(inference, "solve", altered)
    r = run_cell(tiny_root, cell)
    assert not r["correct"] and r["failed"] == r["attempted"]


def test_a_cell_config_and_metric_added_as_files_are_found(tmp_path):
    root = make_root(tmp_path)
    config = json.loads((root / "configs" / "s2v-dense.json").read_text())
    config.update(name="s2v-dense-l3", num_layers=3)
    (root / "configs" / "s2v-dense-l3.json").write_text(json.dumps(config))
    (root / "traffic" / "tiny-er-short.json").write_text(json.dumps(
        {"driver": "solve", "graph": "er_dense", "n": 200, "rho": 0.2,
         "problem": "mvc", "max_d": 4, "max_evals": 3}))
    (root / "workloads" / "tiny-l3.json").write_text(json.dumps(
        {"limits": {"pick_gap": 0.5, "mismatch": 0}}))
    (root / "metrics" / "evals_per_call.infer.py").write_text(
        "def read(ctx):\n"
        "    return ctx.window.counts['evals'] / ctx.window.attempted\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-l3", "config": "s2v-dense-l3",
                               "traffic": "tiny-er-short", "chips": 1,
                               "why": "added by files only"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and not m["name"].startswith("dense"):
            m["workloads"].append("tiny-l3")
    bench["per_layer"].append({
        "name": "evals_per_call.infer", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "fused solve program",
        "moves": "infer_step_ms", "workloads": ["tiny-l3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run_cell(root, "tiny-l3", trace=1)
    assert r["correct"]
    assert r["metrics"]["evals_per_call.infer"]["value"] == 3.0


def _cli(cwd, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "solve-dense-er21k",
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_chip_the_run_refuses_and_prints_no_result():
    p = _cli(REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_without_the_program_the_run_refuses(tmp_path, monkeypatch):
    root = make_root(tmp_path)
    monkeypatch.setattr(harness, "CHECKOUT", tmp_path)
    with pytest.raises(harness.RunError, match="no program"):
        run_cell(root, "tiny-dense")
