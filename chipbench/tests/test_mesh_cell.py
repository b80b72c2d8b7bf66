"""The four-chip cell's pieces on four forced CPU devices: the row
generator against ``graphs.dense_er``, the row-split reference against the
one-device reference, whole runs of a small cell of the ``solve_mesh``
driver (sound, with a planted bad commit, with an altered answer), and the
readers of ``collective_ms.infer`` and ``mp_aggregate_roofline.infer``.

JAX takes its device count at start-up, so the multi-device parts run in
one child process whose readings the tests below assert on."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest
from jax.profiler import ProfileData

from conftest import REPO
from test_scopes import _metadata_plane
from test_trace import _ctx

from chipbench import harness, kernels, scopes, trace

ROOT = pathlib.Path(__file__).resolve().parents[1]

_CHILD = textwrap.dedent("""
    import os, sys, json, pathlib
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/chipbench/tests"]
    import re
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from conftest import make_root, run_cell
    from chipbench import graphs, graphs_rows, reference, reference_rows
    from chipbench import harness

    out = {}
    n = 256
    mesh = jax.make_mesh((1, 4), ("data", "graph"),
                         (jax.sharding.AxisType.Auto,) * 2)

    # the generator: each device's rows are dense_er's rows
    gen = {}
    for seed in (7, 2 ** 31 + 12345):
        rows = graphs_rows.dense_er_rows(n, 0.15, seed, mesh, "graph")
        whole = np.asarray(graphs.dense_er(n, 0.15, seed))
        shards = sorted((s.index[1].start or 0, np.asarray(s.data))
                        for s in rows.addressable_shards)
        gen[str(seed)] = {
            "shapes": [list(d.shape) for _, d in shards],
            "equal": all((d == whole[:, r0:r0 + d.shape[1]]).all()
                         for r0, d in shards),
            "edges": int(whole.sum())}
    out["generator"] = gen

    # the reference on the split graph against the one-device reference
    adj = graphs_rows.dense_er_rows(n, 0.15, 7, mesh, "graph")
    w = graphs.policy_weights(7, 16, n)
    sol = (jnp.arange(n) % 5 == 0).astype(jnp.float32)
    keep = 1.0 - sol
    one = reference.Dense(jnp.asarray(np.asarray(adj)[0]))
    split = reference_rows.DenseRows(adj)
    cand = ((one.degree(keep) > 0) & (sol < 0.5)).astype(jnp.float32)
    diffs = {}
    for precision in reference.PRECISIONS:
        a = reference.scores(w, one, sol, cand, num_layers=2,
                             precision=precision)
        b = reference.scores(w, split, sol, cand, num_layers=2,
                             precision=precision)
        ok = np.isfinite(np.asarray(a))
        scale = float(np.abs(np.asarray(a)[ok]).max())
        diffs[precision] = float(np.abs(np.asarray(a - b)[ok]).max()) / scale
    out["reference_rel_diff"] = diffs
    out["reference_degree_equal"] = bool(
        (np.asarray(one.degree(keep)) == np.asarray(split.degree(keep))).all())
    hlo = reference._step.lower(
        w, split, sol, sol, num_layers=2, max_d=8, adaptive=True,
        control="high").compile().as_text()
    out["reference_whole_graph_arrays"] = len(
        re.findall(rf"f32\\[(?:1,)?{n},{n}\\]", hlo))

    # whole runs of a small cell of the driver
    import tempfile
    tmp = pathlib.Path(tempfile.mkdtemp())
    root = make_root(tmp, cells={"tiny-mesh": ("s2v-dense-sp4",
                                               "tiny-er-rows")})
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["workloads"][0]["chips"] = 4
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "traffic" / "tiny-er-rows.json").write_text(json.dumps(
        {"driver": "solve_mesh", "graph": "er_dense_rows", "n": n,
         "rho": 0.15, "problem": "mvc", "max_d": 8, "max_evals": 8}))
    runs = {"sound": run_cell(root, "tiny-mesh", seed=2 ** 31 + 3),
            "traced": run_cell(root, "tiny-mesh", trace=1)}

    engine = harness.import_program("repro.core.engine")
    graphrep = harness.import_program("repro.core.graphrep")
    inference = harness.import_program("repro.core.inference")

    def planted(name, patch):
        engine._build_solve_step.cache_clear()
        jax.clear_caches()
        undo = patch()
        try:
            runs[name] = run_cell(root, "tiny-mesh")
        finally:
            undo()
            engine._build_solve_step.cache_clear()
            jax.clear_caches()

    def unchanged_commit():
        was = graphrep.DenseRep.commit
        graphrep.DenseRep.commit = lambda self, state, sel: (
            state, jnp.zeros(state.candidate.shape[:1], bool))
        return lambda: setattr(graphrep.DenseRep, "commit", was)

    def altered_answer():
        was = inference.solve

        def altered(*args, **kwargs):
            res = was(*args, **kwargs)
            sol = res.solution.copy()
            sol[0, int(np.argmin(sol[0]))] = 1.0
            res.solution = sol
            return res
        inference.solve = altered
        return lambda: setattr(inference, "solve", was)

    planted("bad_commit", unchanged_commit)
    planted("altered", altered_answer)
    out["runs"] = runs

    # the readings the limits are set from, with the lower-precision
    # controls, on the small cell
    from chipbench import calibrate
    out["controls"] = calibrate.main(
        ["--workload", "tiny-mesh", "--seeds", "5,6", "--controls"],
        root=root, devices=jax.devices())
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _CHILD, str(REPO)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_row_generator_gives_dense_er_rows_on_each_device(child):
    for seed, g in child["generator"].items():
        assert g["shapes"] == [[1, 64, 256]] * 4, seed
        assert g["equal"], seed
        assert g["edges"] > 0


def test_the_row_split_reference_reads_as_the_one_device_reference(child):
    assert child["reference_degree_equal"]
    # the split sums in another order: rounding only, at every precision
    for precision, d in child["reference_rel_diff"].items():
        assert d < 1e-5, precision
    # its compiled step never holds the whole (N, N) graph on a device
    assert child["reference_whole_graph_arrays"] == 0


def test_a_sound_mesh_run_is_correct_and_counts_its_chips(child):
    r = child["runs"]["sound"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"infer_step_ms", "setup_s"}
    assert r["device"]["count"] == 4
    assert r["checks"]["compiles_in_window"]["value"] == 0
    t = child["runs"]["traced"]
    assert t["correct"]
    # the CPU trace has no device plane: only the count-based metric reads
    assert set(t["metrics"]) == {"mfu.infer"}


def test_a_step_that_leaves_the_state_unchanged_is_caught(child):
    r = child["runs"]["bad_commit"]
    assert not r["correct"]
    assert r["checks"]["mismatch"]["value"] > 0


def test_an_answer_altered_where_it_is_produced_is_caught(child):
    r = child["runs"]["altered"]
    assert not r["correct"] and r["failed"] == r["attempted"]


def test_lower_precision_fails_the_limit_and_the_program_passes(child):
    limits = harness.load_json(
        ROOT / "workloads" / "solve-dense-er64k-sp4.json")["limits"]
    for r in child["controls"]:
        assert r["pick_gap"] <= limits["pick_gap"]
        assert r["mismatch"] <= limits["mismatch"]
        # the row-split reference in one bfloat16 pass, along the
        # program's covers
        assert r["control_bf16"] > limits["pick_gap"]


# -- the readers ---------------------------------------------------------------

# Two devices, ns: module jit_solve_fn(1) [0,100) on each.  Device 0 runs
# fusion.1 [10,30) (s2v.embed), all-reduce.1 [30,36) (s2v.embed/mesh
# .collective), all-gather.1 [40,42) (env.commit/mesh.collective) and
# the kernel [50,60); device 1 the same ops, the all-reduce [30,40).
# Host: bench.window [0,100).
_DEVICE = '''
planes {
  id: %ID% name: "/device:TPU:%DEV%"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 30000 duration_ps: %AR% }
    events { metadata_id: 4 offset_ps: 40000 duration_ps: 2000 }
    events { metadata_id: 5 offset_ps: 50000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_solve_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.1 = f32[1,32,256]{2,1,0} all-reduce(f32[1,32,256]{2,1,0} %p), to_apply=%add" } }
  event_metadata { key: 4 value { id: 4 name: "%all-gather.1 = f32[4,1,64]{2,1,0} all-gather(f32[1,1,64]{2,1,0} %p), dimensions={0}" } }
  event_metadata { key: 5 value { id: 5 name: "%KERNEL%" } }
}
'''
_HOST = '''
planes {
  id: 9 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
'''
KERNEL = ('%mp_aggregate.1 = f32[1,32,256]{2,1,0:T(8,128)} custom-call('
          'f32[1,32,64]{2,1,0} %a, f32[1,64,256]{2,1,0} %b), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{f32[1,32,64]{2,1,0}, f32[1,64,256]{2,1,0}}')
_HLO = {"fusion.1": "jit(solve_fn)/while/body/shard_map/s2v.embed/add",
        "all-reduce.1": "jit(solve_fn)/while/body/shard_map/s2v.embed/"
                        "mesh.collective/psum",
        "all-gather.1": "jit(solve_fn)/while/body/shard_map/env.commit/"
                        "mesh.collective/all_gather",
        "mp_aggregate.1": "jit(solve_fn)/while/body/shard_map/s2v.embed/"
                          "pallas_call"}


@pytest.fixture
def split_trace(tmp_path):
    text = "".join(_DEVICE.replace("%ID%", str(d + 1)).replace(
        "%DEV%", str(d)).replace("%AR%", str(ar)).replace(
        "%KERNEL%", KERNEL.replace('"', '\\"'))
        for d, ar in ((0, 6000), (1, 10000))) + _HOST
    cell = tmp_path / ".traces" / "split"
    cell.mkdir(parents=True)
    path = cell / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text)
                     + _metadata_plane({"jit_solve_fn(1)": {1: _HLO}}))
    return tmp_path, path


def _reader(name, root):
    reader = harness.load_module(ROOT / "metrics" / f"{name}.py")
    reader.ROOT = root
    return reader


def test_collective_time_is_read_per_evaluation_over_the_devices(
        split_trace):
    root, path = split_trace
    reduced = trace.reduce(str(path), n_devices=2)
    ctx = _ctx(reduced, evals=4)
    ctx.cell["name"] = "split"
    reader = _reader("collective_ms.infer", root)
    # all-reduce 6 and 10 ns, all-gather 2 ns on each: 10 ns a device
    assert reader.read(ctx) == pytest.approx(10e-9 * 1e3 / 4)
    # the fixed scopes still count the same ops where they did
    scoped = scopes.join(str(path), 2)
    assert scoped.seconds("s2v.embed") == pytest.approx(
        (20 + 8 + 10) * 1e-9)
    assert scoped.seconds("env.commit") == pytest.approx(2e-9)


def test_collective_time_reads_nothing_without_the_scope(split_trace):
    root, path = split_trace
    ctx = _ctx(trace.reduce(str(path), n_devices=2), evals=4)
    ctx.cell["name"] = "none"           # no trace under this cell's name
    assert _reader("collective_ms.infer", root).read(ctx) is None


def test_the_aggregation_kernel_work_is_read_from_its_shapes(split_trace):
    _root, path = split_trace
    reader = _reader("mp_aggregate_roofline.infer", ROOT)
    shapes = kernels.shapes_of(KERNEL)
    flops, nbytes = reader.work(shapes)
    assert flops == 2 * 32 * 64 * 256
    assert nbytes == (32 * 256 + 32 * 64 + 64 * 256) * 4
    ctx = _ctx(trace.reduce(str(path), n_devices=2), evals=4)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    # one call a device of 10 ns each
    assert reader.read(ctx) == pytest.approx(100.0 * least / 10e-9)
