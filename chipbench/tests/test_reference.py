"""The plain reference computes what the program computes, and its replay
accepts the program's own answers and refuses altered ones."""
import numpy as np
import jax.numpy as jnp

from chipbench import graphs, reference
from chipbench.drivers import solve as solve_driver
from chipbench.harness import import_program


def _program_scores(rep_name, graph_input, sol, weights):
    rep = import_program("repro.core.graphrep").get_rep(rep_name)
    inference = import_program("repro.core.inference")
    params = solve_driver.program_params(weights)
    state = inference.init_solve_state(rep, graph_input, "mvc")
    state = rep.state_from_tuples(_source(rep_name, graph_input), [0],
                                  sol[None]) if sol.any() else state
    return np.asarray(rep.scores(params, state, num_layers=2))[0]


def _source(rep_name, graph_input):
    return graph_input if rep_name == "dense" else graph_input


def test_dense_scores_match_the_program():
    w = graphs.policy_weights(4, 32)
    adj = graphs.dense_er(256, 0.15, 4)
    sol = np.zeros(256, np.float32)
    sol[[3, 50, 77]] = 1
    cand = ((np.asarray(adj[0]) * (1 - sol)[None, :]).sum(-1) > 0) \
        & (sol < 0.5)
    want = _program_scores("dense", adj, sol, w)
    got = np.asarray(reference.scores(
        w, reference.Dense(adj[0]), jnp.asarray(sol),
        jnp.asarray(cand, jnp.float32), num_layers=2, precision="highest"))
    assert np.array_equal(np.isfinite(got), cand)
    np.testing.assert_allclose(got[cand], want[cand], rtol=2e-6)


def test_csr_scores_match_the_program():
    w = graphs.policy_weights(5, 32)
    indptr, indices, mask = graphs.ba_csr(1500, 4, 5)
    g = import_program("repro.core.graphs")
    batch = g.CsrGraphBatch(indptr=jnp.asarray(indptr)[None],
                            indices=jnp.asarray(indices)[None],
                            edge_mask=jnp.asarray(mask)[None])
    sol = np.zeros(1500, np.float32)
    sol[[0, 1, 9, 700]] = 1
    rep = import_program("repro.core.graphrep").get_rep("csr")
    state = rep.state_from_tuples(batch, [0], sol[None])
    want = np.asarray(rep.scores(solve_driver.program_params(w), state,
                                 num_layers=2))[0]
    cand = np.asarray(state.candidate[0]) > 0.5
    got = np.asarray(reference.scores(
        w, reference.csr_from_arrays(indptr, indices, mask, chunk=4096),
        jnp.asarray(sol), jnp.asarray(cand, jnp.float32), num_layers=2,
        precision="highest"))
    assert np.array_equal(np.isfinite(got), cand)
    np.testing.assert_allclose(got[cand], want[cand], rtol=2e-6)


def _session(**traffic):
    cell = {"traffic": {"driver": "solve", "graph": "er_dense", "n": 300,
                        "rho": 0.15, "problem": "mvc", "max_d": 8,
                        "max_evals": 8, **traffic},
            "limits": {"pick_gap": 1e-5, "mismatch": 0}}
    config = {"embed_dim": 32, "num_layers": 2, "graph_rep": "dense",
              "kernel": "fused", "compute": "f32"}
    return solve_driver.Session(cell, config, seed=6)


def test_check_follows_the_program_and_counts_broken_trajectories():
    s = _session()
    sol, evals = s.first
    covers = s.trajectory(evals)
    assert np.array_equal(covers[-1], sol) and len(covers) == evals + 1
    good = s.read(covers)
    assert good.mismatch == 0 and good.pick_gap < 1e-5
    # a step committed twice as many nodes as the schedule allows
    doubled = covers[:2] + [np.maximum(covers[2], covers[3])] + covers[3:]
    assert s.read(doubled).mismatch > 0
    # a step that left the cover unchanged
    stuck = covers[:3] + [covers[2]] + covers[4:]
    assert s.read(stuck).mismatch > 0
    # the lowest-scored candidate committed in place of one pick
    swapped = [c.copy() for c in covers]
    first = np.flatnonzero(covers[1])[0]
    spare = np.flatnonzero(covers[-1] == 0)[-1]
    for c in swapped[1:]:
        c[first], c[spare] = 0.0, 1.0
    bad = s.read(swapped)
    assert bad.pick_gap > 1e-3 or bad.mismatch > 0


def test_a_full_solve_must_end_with_every_edge_covered():
    s = _session(max_evals=None)
    sol, evals = s.first
    covers = s.trajectory(evals)
    assert s.read(covers).mismatch == 0
    assert s.read(covers[:-1]).mismatch > 0      # stopped one step early


def test_precision_modes_round_as_named():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).standard_normal((64, 8)),
                    jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = {p: np.abs(np.asarray(reference.mm("ij,jk->ik", a, b, p))
                     - exact).max() for p in reference.PRECISIONS}
    assert err["highest"] < 1e-5 < err["high"] * 1e3
    assert err["highest"] <= err["high"] < err["bf16"]
    assert err["bf16"] > 1e-3
