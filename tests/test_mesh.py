"""2-D ``(data, graph)`` mesh parity (DESIGN.md §10): one fused train step
and one full fused solve must be numerically equivalent across the mesh
shapes (1,1) / (2,1) / (1,2) / (2,2) on BOTH GraphRep backends, and the
serving layer must return identical per-request solutions through a dp>1
mesh.

The ``multidevice``-marked tests run IN-PROCESS at real P>1 — CI runs them
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
``multidevice`` job); in a default single-device session they skip and the
slow subprocess wrapper at the bottom provides the coverage instead.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (Agent, PolicyConfig, engine_init, get_rep,
                        get_train_step, init_policy, mesh_from_spec,
                        normalize_spatial, parse_spatial,
                        random_graph_batch, solve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESHES = [(1, 1), (2, 1), (1, 2), (2, 2)]

multidevice = pytest.mark.multidevice
needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")


def test_normalize_spatial_back_compat():
    """Legacy int P means (1, P); 0/None mean no mesh; tuples pass through."""
    assert normalize_spatial(0) == (1, 1)
    assert normalize_spatial(None) == (1, 1)
    assert normalize_spatial(4) == (1, 4)
    assert normalize_spatial((2, 2)) == (2, 2)
    assert normalize_spatial([2, 1]) == (2, 1)
    assert parse_spatial("4") == 4
    assert parse_spatial("2,2") == (2, 2)
    with pytest.raises(ValueError):
        normalize_spatial((1, 2, 3))


def test_minibatch_divisibility_checked():
    cfg = PolicyConfig(embed_dim=8, minibatch=9, spatial=(2, 1))
    with pytest.raises(ValueError, match="not divisible"):
        get_train_step(cfg, rep="dense")


def _train_params(rep_name, spec, *, n=16, steps=6, tau=2,
                  collectives="auto", target_mode="stored"):
    """Params after `steps` fused train steps (eps=0) on the given mesh
    spec — the DESIGN.md §8 RNG schedule makes this deterministic, so mesh
    shapes AND collective strategies are directly comparable."""
    from repro.core.mesh import shard_batch
    rep = get_rep(rep_name)
    adj = random_graph_batch("er", n, 4, seed=0, rho=0.3)
    cfg = PolicyConfig(embed_dim=8, num_layers=2, minibatch=8,
                       replay_capacity=64, learning_rate=1e-3,
                       eps_start=0.0, eps_end=0.0, graph_rep=rep_name,
                       spatial=spec, collectives=collectives)
    agent = Agent(cfg, num_nodes=n)
    fused = get_train_step(cfg, rep=rep, tau=tau, target_mode=target_mode)
    mesh = mesh_from_spec(spec)
    es = engine_init(cfg, agent.params, agent.opt, n, seed=0, mesh=mesh)
    source = rep.prepare_dataset(adj)
    gi = np.arange(4, dtype=np.int32)
    state = rep.state_from_tuples(source, gi, np.zeros((4, n), np.float32))
    if mesh is not None:
        state = shard_batch(mesh, state)
    losses = []
    for _ in range(steps):
        es, state, _a, _r, _d, loss = fused(es, state, source,
                                            jnp.asarray(gi))
        losses.append(float(loss))
    assert np.isfinite(losses[-1])
    return jax.tree.map(np.asarray, es.params), losses


@multidevice
@needs4
@pytest.mark.parametrize("rep_name", ["dense", "sparse"])
def test_train_step_parity_across_mesh_shapes(rep_name):
    """(1,1) == (2,1) == (1,2) == (2,2) within 1e-6 for the fused train
    step: same actions, same replay contents, params bit-close."""
    base, base_losses = _train_params(rep_name, 0)
    for spec in MESHES[1:]:
        params, losses = _train_params(rep_name, spec)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(params)):
            np.testing.assert_allclose(b, a, atol=1e-6, err_msg=str(spec))
        warm = np.isfinite(base_losses)
        np.testing.assert_allclose(np.asarray(losses)[warm],
                                   np.asarray(base_losses)[warm],
                                   atol=1e-6, err_msg=str(spec))


@multidevice
@needs4
def test_csr_data_parallel_train_parity():
    """csr trains data-parallel only; its per-graph shard_map scoring over
    `data` (acting, targets, GD loss) keeps (2,1) at the one-device
    params."""
    base, base_losses = _train_params("csr", 0)
    params, losses = _train_params("csr", (2, 1))
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(params)):
        np.testing.assert_allclose(b, a, atol=1e-6)
    warm = np.isfinite(base_losses)
    np.testing.assert_allclose(np.asarray(losses)[warm],
                               np.asarray(base_losses)[warm], atol=1e-6)


@multidevice
@needs4
@pytest.mark.parametrize("rep_name", ["dense", "sparse"])
def test_fused_solve_parity_across_mesh_shapes(rep_name):
    """One full adaptive solve is bit-identical (solutions, eval counts,
    commit counts) across every mesh shape, on both representations."""
    adj = random_graph_batch("er", 16, 4, seed=0, rho=0.3)
    params = init_policy(jax.random.key(0), PolicyConfig(embed_dim=8))
    ref = solve(params, adj, num_layers=2, multi_node=True, rep=rep_name,
                engine="host")
    for spec in MESHES:
        res = solve(params, adj, num_layers=2, multi_node=True,
                    rep=rep_name, engine="device", spatial=spec)
        assert (res.solution == ref.solution).all(), spec
        assert res.policy_evals == ref.policy_evals, spec
        assert (res.nodes_committed == ref.nodes_committed).all(), spec


@multidevice
@needs4
@pytest.mark.parametrize("rep_name", ["dense", "sparse"])
def test_manual_vs_gspmd_vs_single_device_parity(rep_name):
    """Three-way parity (DESIGN.md §10): the manual-collective path, the
    gspmd reference and the single-device step agree to ≤1e-6 on loss and
    params at (2,1), (1,2) and (2,2), in both target modes, fused train
    step + a full solve with the trained params."""
    adj = random_graph_batch("er", 16, 4, seed=0, rho=0.3)
    for target_mode in ("stored", "fresh"):
        base, base_losses = _train_params(rep_name, 0,
                                          target_mode=target_mode)
        ref = solve(base, adj, num_layers=2, multi_node=True, rep=rep_name,
                    engine="host")
        for spec in [(2, 1), (1, 2), (2, 2)]:
            for coll in ("manual", "gspmd"):
                params, losses = _train_params(rep_name, spec,
                                               collectives=coll,
                                               target_mode=target_mode)
                tag = f"{spec} {coll} {target_mode}"
                for a, b in zip(jax.tree.leaves(base),
                                jax.tree.leaves(params)):
                    np.testing.assert_allclose(b, a, atol=1e-6, err_msg=tag)
                warm = np.isfinite(base_losses)
                np.testing.assert_allclose(np.asarray(losses)[warm],
                                           np.asarray(base_losses)[warm],
                                           atol=1e-6, err_msg=tag)
                res = solve(params, adj, num_layers=2, multi_node=True,
                            rep=rep_name, engine="device", spatial=spec)
                assert (res.solution == ref.solution).all(), tag
                assert res.policy_evals == ref.policy_evals, tag


@multidevice
@needs4
def test_manual_collectives_reject_unsupported_configs():
    """Explicit collectives='manual' fails fast where the path cannot run:
    candidate_fn envs (mds) and the csr rep; the csr fail-fast names the
    manual path's status.  'auto' falls back silently for mds."""
    from repro.core import engine as engine_mod
    cfg = PolicyConfig(embed_dim=8, minibatch=8, replay_capacity=64,
                       spatial=(2, 2), collectives="manual")
    with pytest.raises(ValueError, match="candidate_fn"):
        get_train_step(cfg, rep="dense", problem="mds")
    # auto → gspmd fallback builds fine
    cfg_auto = PolicyConfig(embed_dim=8, minibatch=8, replay_capacity=64,
                            spatial=(2, 2))
    assert get_train_step(cfg_auto, rep="dense", problem="mds") is not None
    cfg_csr = PolicyConfig(embed_dim=8, minibatch=8, replay_capacity=64,
                           spatial=(2, 1), graph_rep="csr",
                           collectives="manual")
    with pytest.raises(ValueError, match="csr"):
        get_train_step(cfg_csr, rep="csr")
    with pytest.raises(ValueError, match="manual"):
        engine_mod._check_csr_spatial(get_rep("csr"), 2)


@multidevice
@needs4
def test_no_unusable_donation_warnings():
    """The donation contract (DESIGN.md §10): every buffer the fused
    train step and solve donate (engine carry, episode state, solve
    state) aliases an output — compiling and running them on the (2,2)
    mesh and on a single device emits no "donated buffers were not
    usable" warnings."""
    import warnings as warnings_mod
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        _train_params("dense", (2, 2), steps=3, tau=1)
        _train_params("dense", 0, steps=3, tau=1)
        adj = random_graph_batch("er", 16, 4, seed=1, rho=0.3)
        params = init_policy(jax.random.key(1), PolicyConfig(embed_dim=8))
        solve(params, adj, num_layers=2, multi_node=True, rep="dense",
              engine="device", spatial=(2, 2))
        solve(params, adj, num_layers=2, multi_node=True, rep="dense",
              engine="device", spatial=0)
    bad = [str(w.message) for w in rec
           if "donated" in str(w.message).lower()]
    assert not bad, bad


@multidevice
@needs4
def test_serving_through_data_axis_matches_single_device():
    """A dp>1 service (max_batch per-device, rows spread over `data`)
    returns identical per-request solutions to the single-device service
    with the same total rows per dispatch."""
    from repro.serving import GraphSolverService
    params = init_policy(jax.random.key(3), PolicyConfig(embed_dim=8))
    rng = np.random.default_rng(0)
    adjs = [random_graph_batch("er", int(n), 1, seed=i, rho=0.3)[0]
            for i, n in enumerate(rng.integers(5, 14, size=6))]

    svc1 = GraphSolverService(params, PolicyConfig(embed_dim=8, spatial=0),
                              multi_node=True, max_batch=4)
    svc2 = GraphSolverService(
        params, PolicyConfig(embed_dim=8, spatial=(2, 1)),
        multi_node=True, max_batch=2)
    assert svc2.rows_per_dispatch == svc1.rows_per_dispatch == 4

    r1 = svc1.serve(adjs)
    r2 = svc2.serve(adjs)
    for a, b in zip(r1, r2):
        assert a.id == b.id and a.size == b.size
        np.testing.assert_array_equal(a.solution, b.solution)
    assert svc2.stats.batches == svc1.stats.batches


@multidevice
@needs4
def test_serving_2d_mesh_solutions_valid():
    """Full 2-D mesh serving (dp=2, sp=2): every response is a valid cover
    of its request graph and matches the single-device service."""
    from repro.core.env import is_cover
    from repro.serving import GraphSolverService
    params = init_policy(jax.random.key(3), PolicyConfig(embed_dim=8))
    adjs = [random_graph_batch("er", n, 1, seed=s, rho=0.3)[0]
            for s, n in enumerate((8, 12, 16, 12))]
    ref = GraphSolverService(params, PolicyConfig(embed_dim=8, spatial=0),
                             multi_node=True, max_batch=4).serve(adjs)
    svc = GraphSolverService(
        params, PolicyConfig(embed_dim=8, spatial=(2, 2)),
        multi_node=True, max_batch=2)
    out = svc.serve(adjs)
    for a, r, b in zip(adjs, ref, out):
        np.testing.assert_array_equal(r.solution, b.solution)
        assert bool(np.asarray(is_cover(jnp.asarray(a)[None],
                                        jnp.asarray(b.solution,
                                                    jnp.float32)[None]))[0])


@multidevice
@needs4
@pytest.mark.parametrize("problem", ["mis", "mds"])
@pytest.mark.parametrize("rep_name", ["dense", "sparse"])
def test_new_env_solve_parity_across_mesh_shapes(problem, rep_name):
    """The extension environments ride the same 2-D mesh contract: one
    full adaptive solve is bit-identical across every mesh shape and
    checker-feasible, on both representations."""
    from repro.core import env as env_lib
    adj = random_graph_batch("er", 16, 4, seed=0, rho=0.3)
    params = init_policy(jax.random.key(0), PolicyConfig(embed_dim=8))
    ref = solve(params, adj, num_layers=2, multi_node=True, rep=rep_name,
                problem=problem, engine="host")
    assert np.asarray(env_lib.checker(problem)(
        jnp.asarray(adj), jnp.asarray(ref.solution))).all()
    for spec in MESHES:
        res = solve(params, adj, num_layers=2, multi_node=True,
                    rep=rep_name, problem=problem, engine="device",
                    spatial=spec)
        assert (res.solution == ref.solution).all(), spec
        assert res.policy_evals == ref.policy_evals, spec


@multidevice
@needs4
def test_gspmd_mispartitioning_canary():
    """The gspmd reference path on the full 2-D mesh, with no operand
    staged at the shard_map boundary, trains to the single-device params
    (≤1e-6, dense, both target modes).  Older JAX mispartitioned the
    in-jit gathered minibatch here (order-1e-3 errors); this guards the
    retirement of that workaround."""
    for target_mode in ("stored", "fresh"):
        base, _ = _train_params("dense", 0, target_mode=target_mode)
        gspmd, _ = _train_params("dense", (2, 2), collectives="gspmd",
                                 target_mode=target_mode)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(gspmd)):
            np.testing.assert_allclose(b, a, atol=1e-6, err_msg=target_mode)


@multidevice
@needs4
def test_replay_and_state_actually_sharded_over_mesh():
    """The memory claim behind the 2-D mesh: with dp=2 the device-resident
    replay holds half the tuple rows per device, and sp=2 halves the mask
    columns."""
    from repro.core import shard_replay, make_mesh
    from repro.core.replay import device_replay_init
    mesh = make_mesh(2, 2)
    replay = shard_replay(mesh, device_replay_init(64, 16))
    shard = replay.solution.addressable_shards[0].data.shape
    assert shard == (32, 8)                       # (R/dp, N/sp)
    assert replay.graph_idx.addressable_shards[0].data.shape == (32,)


@pytest.mark.slow
def test_mesh_parity_under_forced_four_devices():
    """Subprocess fallback for single-device sessions: run the multidevice
    subset of this file under a forced 4-device CPU topology and require
    that tests actually ran and passed (CI's `multidevice` job runs the
    same subset in-process)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "multidevice",
         os.path.join(REPO, "tests", "test_mesh.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1500)
    tail = (out.stdout + out.stderr)[-3000:]
    assert out.returncode == 0, tail
    summary = [l for l in out.stdout.strip().splitlines() if "passed" in l]
    assert summary, f"multidevice subset did not run: {tail}"
    assert "failed" not in summary[-1] and "skipped" not in summary[-1], tail
