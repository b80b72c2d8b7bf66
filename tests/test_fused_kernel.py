"""Fused S2V super-kernel path (DESIGN.md §12).

Covers the full acceptance surface of the fused layer: Pallas-kernel parity
against the ``repro.kernels.ref`` oracles across rep × dtype × tile ×
padded-row cases, fused-vs-"xla" equality through policy scores and full
solves on both GraphRep backends, custom_vjp gradient parity (the TPU
super-kernel's backward is the jnp composition), padding inertness through
the fused path, the bf16 quality-parity gate over the four-problem suite,
and fused-vs-xla parity across 2-D mesh shapes (multidevice job).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (PolicyConfig, init_policy, init_state,
                        policy_scores, random_graph_batch, solve)
from repro.core import env as env_lib
from repro.core.env import cut_value
from repro.core.graphs import sparse_batch_from_dense
from repro.core.s2v import (_dense_layer_hw, _dense_layer_jnp, _agg_hw,
                            _agg_jnp, check_kernel, compute_dtype)
from repro.core.s2v_sparse import _sparse_layer_hw, _sparse_layer_jnp
from repro.kernels import ops, ref
from repro.kernels.s2v_fused import (DENSE_VMEM_BUDGET, dense_tiles,
                                     dense_vmem_bytes)

RNG = np.random.default_rng(7)
REPS = ("dense", "sparse")
PROBLEMS = ("mvc", "maxcut", "mis", "mds")

# Rounding tolerance for a bf16-operand matmul with f32 accumulation:
# one bf16 quantization (2^-8 relative) on each operand.
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(shape):
    return (RNG.random(shape, np.float32) - 0.5).astype(np.float32)


def _dense_case(b=2, k=16, n=40, rho=0.3):
    embed = _rand((b, k, n))
    adj = (RNG.random((b, n, n)) < rho).astype(np.float32)
    base = _rand((b, k, n))
    t4 = _rand((k, k)) * 0.2
    return t4, embed, adj, base


def _sparse_case(b=2, k=16, n=40, rho=0.3):
    """Realistic padded neighbor lists (padded ids == n) via the production
    converter, plus random embeddings/edge factors."""
    adj = (RNG.random((b, n, n)) < rho).astype(np.float32)
    adj = np.maximum(adj, adj.transpose(0, 2, 1))
    np.einsum("bii->bi", adj)[:] = 0
    g = sparse_batch_from_dense(jnp.asarray(adj))
    x = _rand((b, k, n))
    edge = np.asarray(g.valid, np.float32) * RNG.random(
        g.valid.shape).astype(np.float32)
    base = _rand((b, k, n))
    t4 = _rand((k, k)) * 0.2
    return t4, x, np.asarray(g.neighbors), edge, base


# ---------------------------------------------------------------------------
# Kernel vs oracle (interpret mode off-TPU), rep × dtype × tile.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compute", ["f32", "bf16"])
@pytest.mark.parametrize("tile", [8, 16, 128])
def test_fused_dense_kernel_vs_oracle(compute, tile):
    t4, embed, adj, base = _dense_case()
    cd = compute_dtype(compute)
    out = np.asarray(ops.fused_s2v_layer(t4, embed, adj, base, tile_n=tile,
                                         tile_l=tile, compute_dtype=cd))
    want = np.asarray(ref.s2v_layer(t4, embed, adj, base))
    tol = BF16_TOL if compute == "bf16" else dict(rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out, want, **tol)


# (Nl, N, tile_n, tile_l): the shape-chosen blocks, and explicit blocks
# that leave a partial last block on both node axes.  Interpret mode fills
# the part of a block past the array with NaN, so these fail if the
# kernels' edge mask is missing.
DENSE_BLOCKS = [(300, 300, None, None), (300, 300, 256, 128),
                (200, 300, None, None), (200, 300, 256, 128)]


@pytest.mark.parametrize("kernel", ["fused", "aggregate"])
@pytest.mark.parametrize("compute", ["f32", "bf16"])
@pytest.mark.parametrize("nl,n,tile_n,tile_l", DENSE_BLOCKS)
def test_dense_kernels_blocks_vs_oracle(kernel, compute, nl, n, tile_n,
                                        tile_l):
    b, k = 2, 16
    embed = _rand((b, k, nl))
    adj = (RNG.random((b, nl, n)) < 0.3).astype(np.float32)
    cd = compute_dtype(compute)
    if kernel == "fused":
        t4, base = _rand((k, k)) * 0.2, _rand((b, k, n))
        out = ops.fused_s2v_layer(t4, embed, adj, base, tile_n=tile_n,
                                  tile_l=tile_l, compute_dtype=cd)
        want = ref.s2v_layer(t4, embed, adj, base)
    else:
        out = ops.mp_aggregate(embed, adj, tile_n=tile_n, tile_l=tile_l,
                               compute_dtype=cd)
        want = ref.mp_aggregate(embed, adj)
    assert out.shape == (b, k, n)
    tol = BF16_TOL if compute == "bf16" else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **tol)


def test_dense_tiles_follow_the_shapes():
    """Legal TPU blocks (the full dim or a multiple of 128): one block per
    graph at the train shape, and a grid of hundreds of steps, not tens of
    thousands, at the paper's largest graph (W1, N=21,000)."""
    k = 32
    for cd in (jnp.float32, jnp.bfloat16):
        for epilogue in (True, False):
            assert dense_tiles(k, 1024, 1024, epilogue=epilogue,
                               compute_dtype=cd) == (1024, 1024)
            for nl, n in ((21_000, 21_000), (5_250, 21_000)):
                tn, tl = dense_tiles(k, n, nl, epilogue=epilogue,
                                     compute_dtype=cd)
                assert tn == n or tn % 128 == 0
                assert tl == nl or tl % 128 == 0
                assert -(-n // tn) * -(-nl // tl) <= 1000
                assert dense_vmem_bytes(
                    k, epilogue=epilogue, tile_n=tn, tile_l=tl,
                    compute_dtype=cd) <= DENSE_VMEM_BUDGET


@pytest.mark.parametrize("compute", ["f32", "bf16"])
@pytest.mark.parametrize("tile", [8, 16, 128])
def test_fused_sparse_kernel_vs_oracle(compute, tile):
    t4, x, nbr, edge, base = _sparse_case()
    cd = compute_dtype(compute)
    out = np.asarray(ops.fused_s2v_layer_sparse(t4, x, nbr, edge, base,
                                                tile_n=tile,
                                                compute_dtype=cd))
    want = np.asarray(ref.s2v_layer_sparse(t4, x, nbr, edge, base))
    tol = BF16_TOL if compute == "bf16" else dict(rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out, want, **tol)


def test_fused_sparse_kernel_padded_ids_inert():
    """Padded neighbor slots (id == N) must contribute exactly zero even
    with NONZERO edge factors in the padded slots — the kernel's iota
    one-hot is sentinel-free, so id N matches no column in [0, N)."""
    t4, x, nbr, edge, base = _sparse_case()
    n = x.shape[-1]
    hot = edge.copy()
    hot[nbr == n] = 7.0                     # poison the padding slots
    out = np.asarray(ops.fused_s2v_layer_sparse(t4, x, nbr, hot, base))
    want = np.asarray(ops.fused_s2v_layer_sparse(t4, x, nbr, edge, base))
    np.testing.assert_array_equal(out, want)


def test_fused_dense_kernel_isolated_rows():
    """All-zero adjacency rows/cols (isolated padding nodes) come out as
    relu(base) exactly — the fused epilogue adds a zero aggregate."""
    t4, embed, adj, base = _dense_case(n=24)
    adj[:, :, 16:] = 0.0
    adj[:, 16:, :] = 0.0
    out = np.asarray(ops.fused_s2v_layer(t4, embed, adj, base,
                                         tile_n=8, tile_l=8))
    np.testing.assert_array_equal(out[:, :, 16:],
                                  np.maximum(base[:, :, 16:], 0.0))


# ---------------------------------------------------------------------------
# custom_vjp gradient parity: the TPU super-kernel's backward is the jnp
# composition — grads through the hw wrapper (kernel forward, interpret mode
# off-TPU) must match grads through the pure jnp lowering.
# ---------------------------------------------------------------------------

def _grad_check(fn_hw, fn_jnp, args, wrt):
    g_hw = jax.grad(lambda *a: fn_hw(*a).sum(), argnums=wrt)(*args)
    g_jn = jax.grad(lambda *a: fn_jnp(*a).sum(), argnums=wrt)(*args)
    for a, b in zip(jax.tree.leaves(g_hw), jax.tree.leaves(g_jn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_dense_layer_custom_vjp_grad_parity():
    t4, embed, adj, base = _dense_case(b=1, k=8, n=24)
    cd = jnp.float32
    _grad_check(lambda *a: _dense_layer_hw(*a, cd),
                lambda *a: _dense_layer_jnp(*a, cd),
                (jnp.asarray(t4), jnp.asarray(embed), jnp.asarray(adj),
                 jnp.asarray(base)), (0, 1, 2, 3))


def test_agg_custom_vjp_grad_parity():
    _, embed, adj, _ = _dense_case(b=1, k=8, n=24)
    cd = jnp.float32
    _grad_check(lambda *a: _agg_hw(*a, cd), lambda *a: _agg_jnp(*a, cd),
                (jnp.asarray(embed), jnp.asarray(adj)), (0, 1))


def test_sparse_layer_custom_vjp_grad_parity():
    t4, x, nbr, edge, base = _sparse_case(b=1, k=8, n=24)
    cd = jnp.float32
    _grad_check(
        lambda t, xx, e, b: _sparse_layer_hw(t, xx, jnp.asarray(nbr), e,
                                             b, cd),
        lambda t, xx, e, b: _sparse_layer_jnp(t, xx, jnp.asarray(nbr), e,
                                              b, cd),
        (jnp.asarray(t4), jnp.asarray(x), jnp.asarray(edge),
         jnp.asarray(base)), (0, 1, 2, 3))


# ---------------------------------------------------------------------------
# Fused vs "xla" reference chain through the policy entry points.  At f32
# the fused lowering is the same op sequence (layer-0 elision is exact:
# zero-initialized embeddings make the first aggregation identically zero),
# so we assert VALUE EQUALITY, not allclose.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    adj = random_graph_batch("er", 32, 4, seed=0, rho=0.25)
    params = init_policy(jax.random.key(0), PolicyConfig(embed_dim=16))
    return adj, params


@pytest.mark.parametrize("rep", REPS)
@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_policy_scores_fused_equals_xla(setup, rep, num_layers):
    from repro.core.graphrep import get_rep
    from repro.core.inference import init_solve_state
    adj, params = setup
    r = get_rep(rep)
    st = init_solve_state(r, adj, "mvc")
    want = r.scores(params, st, num_layers=num_layers, kernel="xla")
    got = r.scores(params, st, num_layers=num_layers, kernel="fused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rep", REPS)
@pytest.mark.parametrize("problem", PROBLEMS)
def test_solve_fused_equals_xla(setup, rep, problem):
    """Full adaptive solves agree action-for-action between the fused
    super-kernel path and the reference chain, on both backends and all
    four environments."""
    adj, params = setup
    a = solve(params, adj, num_layers=2, multi_node=True, rep=rep,
              problem=problem, kernel="xla")
    b = solve(params, adj, num_layers=2, multi_node=True, rep=rep,
              problem=problem, kernel="fused")
    np.testing.assert_array_equal(a.solution, b.solution)
    assert a.policy_evals == b.policy_evals
    np.testing.assert_array_equal(a.nodes_committed, b.nodes_committed)


def test_fused_solve_padding_inert(setup):
    """Isolated padding rows stay uncommitted through the fused path."""
    _, params = setup
    adj = random_graph_batch("er", 20, 2, seed=3, rho=0.3)
    pad = np.zeros((2, 32, 32), np.float32)
    pad[:, :20, :20] = adj
    res = solve(params, pad, num_layers=2, multi_node=True, kernel="fused")
    assert res.solution[:, 20:].sum() == 0


def test_kernel_and_compute_validated():
    with pytest.raises(ValueError, match="unknown kernel"):
        check_kernel("cuda")
    with pytest.raises(ValueError, match="unknown compute"):
        compute_dtype("fp8")
    with pytest.raises(ValueError, match="unknown kernel"):
        PolicyConfig(embed_dim=8, kernel="cuda")
    with pytest.raises(ValueError, match="unknown compute"):
        PolicyConfig(embed_dim=8, compute="fp8")


def test_graphrep_config_stamps_kernel_selection():
    from repro.configs.base import GraphRepConfig
    cfg = GraphRepConfig(rep="sparse", kernel="xla", compute="bf16").apply(
        PolicyConfig(embed_dim=8))
    assert cfg.kernel == "xla" and cfg.compute == "bf16"
    assert cfg.graph_rep == "sparse"


# ---------------------------------------------------------------------------
# bf16 quality-parity gate (ISSUE acceptance): across the four-problem
# suite, bf16-compute solves must be feasible and land within 10% mean
# objective of the f32 solves (tolerance stated in DESIGN.md §12).
# ---------------------------------------------------------------------------

def _objective(problem, adj, solution):
    if problem == "maxcut":
        return np.asarray(cut_value(jnp.asarray(adj),
                                    jnp.asarray(solution, jnp.float32)))
    return np.asarray(solution).sum(-1)


@pytest.mark.parametrize("problem", PROBLEMS)
def test_bf16_quality_gate(problem):
    adj = random_graph_batch("er", 32, 8, seed=11, rho=0.25)
    params = init_policy(jax.random.key(2), PolicyConfig(embed_dim=16))
    f32 = solve(params, adj, num_layers=2, multi_node=True,
                problem=problem, compute="f32")
    b16 = solve(params, adj, num_layers=2, multi_node=True,
                problem=problem, compute="bf16")
    ok = env_lib.checker(problem)(jnp.asarray(adj),
                                  jnp.asarray(b16.solution))
    assert np.asarray(ok).all(), "bf16 solutions must stay feasible"
    obj_f32 = _objective(problem, adj, f32.solution).mean()
    obj_b16 = _objective(problem, adj, b16.solution).mean()
    assert abs(obj_b16 - obj_f32) <= 0.10 * abs(obj_f32) + 1e-9, (
        f"{problem}: bf16 mean objective {obj_b16} vs f32 {obj_f32}")


# ---------------------------------------------------------------------------
# Mesh parity (CI multidevice job: XLA_FLAGS=--xla_force_host_platform_
# device_count=4): the fused path's sharded lowering — psum-split dense
# epilogue, all-gather-then-fuse sparse — must agree with the xla chain.
# ---------------------------------------------------------------------------

multidevice = pytest.mark.multidevice
needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")


@multidevice
@needs4
@pytest.mark.parametrize("rep", REPS)
def test_mesh_solve_fused_equals_xla(rep):
    adj = random_graph_batch("er", 16, 4, seed=0, rho=0.3)
    params = init_policy(jax.random.key(0), PolicyConfig(embed_dim=8))
    for spec in [(2, 1), (1, 2), (2, 2)]:
        a = solve(params, adj, num_layers=2, multi_node=True, rep=rep,
                  engine="device", spatial=spec, kernel="xla")
        b = solve(params, adj, num_layers=2, multi_node=True, rep=rep,
                  engine="device", spatial=spec, kernel="fused")
        np.testing.assert_array_equal(a.solution, b.solution,
                                      err_msg=f"{rep} {spec}")
        assert a.policy_evals == b.policy_evals


@multidevice
@needs4
@pytest.mark.parametrize("rep", REPS)
def test_mesh_train_fused_equals_single_device(rep):
    """Fused-kernel training on the (2,2) mesh matches single-device fused
    training (the sharded dense path splits fusion at the psum precisely to
    keep this true)."""
    from repro.core import (Agent, engine_init, get_rep, get_train_step,
                            mesh_from_spec)
    n = 16
    rep_obj = get_rep(rep)
    adj = random_graph_batch("er", n, 4, seed=0, rho=0.3)

    def run(spec):
        cfg = PolicyConfig(embed_dim=8, num_layers=2, minibatch=8,
                           replay_capacity=64, learning_rate=1e-3,
                           eps_start=0.0, eps_end=0.0, graph_rep=rep,
                           spatial=spec)
        agent = Agent(cfg, num_nodes=n)
        fused = get_train_step(cfg, rep=rep_obj, tau=2, target_mode="stored")
        es = engine_init(cfg, agent.params, agent.opt, n, seed=0,
                         mesh=mesh_from_spec(spec))
        source = rep_obj.prepare_dataset(adj)
        gi = np.arange(4, dtype=np.int32)
        state = rep_obj.state_from_tuples(source, gi,
                                          np.zeros((4, n), np.float32))
        for _ in range(4):
            es, state, *_rest = fused(es, state, source, jnp.asarray(gi))
        return jax.tree.map(np.asarray, es.params)

    base = run(0)
    mesh = run((2, 2))
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(mesh)):
        np.testing.assert_allclose(b, a, atol=1e-6)
