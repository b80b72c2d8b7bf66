"""End-to-end spatial inference: the FULL Alg. 4 solve loop driven by the
P-way partitioned scorer must produce identical solutions to the
single-device path (subprocess with forced host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow      # subprocess + forced 4-device shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import (PolicyConfig, init_policy, init_state,
                            random_graph_batch, solve, make_graph_mesh,
                            spatial_scores_fn, shard_graph_arrays)
    from repro.core.env import is_cover
    from repro.core.inference import _inference_step
    from repro.core.graphs import GraphState

    adj = random_graph_batch("er", 24, 2, seed=5, rho=0.25)
    params = init_policy(jax.random.key(2), PolicyConfig(embed_dim=16))

    # single-device reference solve
    ref = solve(params, adj, num_layers=2, multi_node=False)

    # spatial solve: scores from the P-way partitioned path, state update on
    # host (mirrors paper Fig. 4: all devices apply the same argmax)
    mesh = make_graph_mesh(4)
    scorer = spatial_scores_fn(mesh, num_layers=2)
    state = init_state(jnp.asarray(adj))
    for _ in range(24):
        a, s, c = shard_graph_arrays(mesh, state.adj, state.solution,
                                     state.candidate)
        scores = scorer(params, a, s, c)
        # identical commit rule as the jitted d=1 step
        v = jnp.argmax(scores, axis=-1)
        sel = jax.nn.one_hot(v, 24)
        active = state.candidate.sum(-1) > 0
        sel = sel * active[:, None]
        solution = jnp.maximum(state.solution, sel)
        keep = 1.0 - sel
        new_adj = state.adj * keep[:, :, None] * keep[:, None, :]
        deg = new_adj.sum(-1)
        cand = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
        state = GraphState(adj=new_adj, candidate=cand, solution=solution)
        if float(new_adj.sum()) == 0:
            break
    sizes = np.asarray(state.solution.sum(-1)).astype(int).tolist()
    covered = bool(np.asarray(is_cover(jnp.asarray(adj),
                                       state.solution)).all())
    print(json.dumps({"ref": ref.sizes.tolist(), "spatial": sizes,
                      "covered": covered}))
""")


def test_spatial_solve_matches_single_device():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["covered"]
    assert res["spatial"] == res["ref"]


_CHILD_SPARSE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import (PolicyConfig, init_policy, random_graph_batch,
                            solve, make_graph_mesh, sparse_spatial_scores_fn,
                            shard_sparse_arrays, SPARSE)
    from repro.core.env import is_cover
    from repro.core.graphs import SparseGraphState

    n = 24
    adj = random_graph_batch("er", n, 2, seed=5, rho=0.25)
    params = init_policy(jax.random.key(2), PolicyConfig(embed_dim=16))

    # single-device sparse-rep reference solve (unified Alg. 4 driver)
    ref = solve(params, adj, num_layers=2, multi_node=False, rep="sparse")

    # spatial sparse solve: each device holds its (B, N/P, D) neighbor-list
    # rows (the paper's distributed sparse graph storage, Fig. 2 + SS4.1);
    # scores come from the P-way shard_map, the commit runs on host.
    mesh = make_graph_mesh(4)
    scorer = sparse_spatial_scores_fn(mesh, num_layers=2)
    state = SPARSE.init_state(adj)
    score_diff = 0.0
    single_scores = SPARSE.scores(params, state, num_layers=2)
    for it in range(n):
        nb, va, so, ca = shard_sparse_arrays(
            mesh, state.neighbors, state.valid, state.solution,
            state.candidate)
        scores = scorer(params, nb, va, so, ca)
        if it == 0:
            score_diff = float(jnp.abs(scores - single_scores).max())
        v = jnp.argmax(scores, axis=-1)
        active = state.candidate.sum(-1) > 0
        sel = jax.nn.one_hot(v, n) * active[:, None]
        state, done = SPARSE.commit(state, sel)
        if bool(np.asarray(done).all()):
            break
    sizes = np.asarray(state.solution.sum(-1)).astype(int).tolist()
    covered = bool(np.asarray(is_cover(jnp.asarray(adj),
                                       state.solution)).all())
    shard_shape = list(nb.addressable_shards[0].data.shape)
    print(json.dumps({"ref": ref.sizes.tolist(), "spatial": sizes,
                      "covered": covered, "score_diff": score_diff,
                      "shard_shape": shard_shape}))
""")


def test_sparse_spatial_solve_matches_single_device():
    """The paper's distributed sparse storage: (B, N/P, D) neighbor-list
    sharding under shard_map must reproduce the single-device sparse path."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD_SPARSE],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["covered"]
    assert res["spatial"] == res["ref"]
    assert res["score_diff"] < 1e-4
    # per-device block really is (B, N/P, D)
    assert res["shard_shape"][1] == 24 // 4


_CHILD_ROWS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import (PolicyConfig, init_policy, random_graph_batch,
                            solve, env)
    from repro.core import inference
    from repro.core.engine import get_solve_step
    from repro.core.graphrep import DENSE
    from repro.core.mesh import make_mesh, shard_rows, shard_solve_state

    n, sp = 256, 4
    adj = random_graph_batch("er", n, 1, seed=3, rho=0.05)
    params = init_policy(jax.random.key(4), PolicyConfig(embed_dim=16))
    mesh = make_mesh(1, sp)
    out = {"problems": {}}
    for problem in ("mvc", "maxcut", "mis", "mds"):
        kw = dict(num_layers=2, multi_node=True, rep="dense",
                  problem=problem)
        one = solve(params, adj, **kw)
        plain = solve(params, adj, engine="host", kernel="xla", **kw)
        split = solve(params, shard_rows(mesh, adj), spatial=(1, sp), **kw)
        out["problems"][problem] = {
            name: {"solution": r.solution.astype(int).tolist(),
                   "evals": r.policy_evals,
                   "committed": r.nodes_committed.tolist()}
            for name, r in (("one", one), ("plain", plain),
                            ("split", split))}
        out["problems"][problem]["feasible"] = bool(np.asarray(
            env.checker(problem)(jnp.asarray(adj),
                                 jnp.asarray(split.solution))).all())

    # the carry and the compiled program hold N/sp rows per device
    state = shard_solve_state(mesh, inference.init_solve_state(
        DENSE, shard_rows(mesh, adj), "mvc"))
    fn = get_solve_step(rep=DENSE, problem="mvc", num_layers=2,
                        use_adaptive=True, spatial=(1, sp), donate=False)
    compiled = fn.lower(params, state, jnp.int32(n)).compile()
    text = compiled.as_text()
    final, evals, _ = fn(params, state, jnp.int32(n))
    out["final_shards"] = sorted({tuple(s.data.shape)
                                  for s in final.adj.addressable_shards})
    out["argument_bytes"] = compiled.memory_analysis().argument_size_in_bytes
    out["whole_graph_arrays"] = len(re.findall(rf"f32\\[1,{n},{n}\\]", text))
    out["collectives"] = sorted(set(re.findall(
        r"= (\\S+) (?:all-gather|all-reduce|all-to-all|collective-permute"
        r"|reduce-scatter)", text)))

    # an adjacency not laid out by rows is placed by rows: what the
    # solve's state init receives is split, never replicated
    seen = []
    init = inference.init_solve_state
    inference.init_solve_state = lambda rep, a, p: seen.append(sorted(
        {tuple(s.data.shape) for s in a.addressable_shards})) or init(
            rep, a, p)
    solve(params, adj, spatial=(1, sp), **kw)
    solve(params, jax.device_put(adj, jax.devices()[0]), spatial=(1, sp),
          **kw)
    inference.init_solve_state = init
    out["placed_shards"] = seen
    laid = shard_rows(mesh, adj)
    out["laid_out_kept"] = shard_rows(mesh, laid) is laid
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def rows_child():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD_ROWS],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("problem", ["mvc", "maxcut", "mis", "mds"])
def test_row_split_dense_solve_matches_one_device_and_plain_reference(
        rows_child, problem):
    """At N=256 over 4 devices the row-split solve (each device holding
    and rewriting its 64 rows) gives the one-device fused solve's and the
    host engine's unfused f32 chain's solutions, evaluation counts and
    commit counts, and a feasible answer."""
    r = rows_child["problems"][problem]
    assert r["feasible"]
    for ref in ("one", "plain"):
        assert r["split"]["solution"] == r[ref]["solution"], ref
        assert r["split"]["evals"] == r[ref]["evals"], ref
        assert r["split"]["committed"] == r[ref]["committed"], ref


def test_row_split_solve_carry_and_program_hold_a_quarter_of_the_graph(
        rows_child):
    n, sp = 256, 4
    adj_bytes = 4 * n * n
    # every device's shard of the returned state's adjacency: (B, N/sp, N)
    assert rows_child["final_shards"] == [[1, n // sp, n]]
    # arguments per device: its rows plus the (B, N) masks and weights
    assert adj_bytes / sp <= rows_child["argument_bytes"] \
        < 1.1 * adj_bytes / sp
    # no array of the whole graph, and no collective moves rows: each is
    # of (B, K, N) partials, (B, N) vectors or smaller
    assert rows_child["whole_graph_arrays"] == 0
    for shape in rows_child["collectives"]:
        dims = [int(d) for d in shape.split("[")[1].split("]")[0].split(",")
                if d]
        assert np.prod(dims) <= 16 * n, shape


def test_an_adjacency_not_laid_out_by_rows_is_placed_by_rows(rows_child):
    # a host array and a one-device array each reach the state init split
    assert rows_child["placed_shards"] == [[[1, 64, 256]], [[1, 64, 256]]]
    assert rows_child["laid_out_kept"]
