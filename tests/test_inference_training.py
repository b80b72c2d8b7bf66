import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (Agent, PolicyConfig, init_policy, init_state,
                        random_graph_batch, solve, adaptive_d, train_agent,
                        evaluate_quality)
from repro.core.env import is_cover
from repro.core.solvers import (greedy_mvc, greedy_mvc_batch,
                                matching_2approx, exact_mvc_size,
                                mvc_lower_bound, reference_sizes)


def test_adaptive_d_schedule():
    n = 64
    d = adaptive_d(jnp.asarray([40, 33, 20, 17, 10, 9, 8, 1, 0]), n)
    assert np.asarray(d).tolist() == [8, 8, 4, 4, 2, 2, 1, 1, 1]


def test_solve_produces_cover_d1_and_adaptive():
    adj = random_graph_batch("er", 30, 4, seed=0, rho=0.2)
    params = init_policy(jax.random.key(0), PolicyConfig(embed_dim=8))
    for mn in (False, True):
        res = solve(params, adj, num_layers=2, multi_node=mn)
        assert np.asarray(is_cover(jnp.asarray(adj), jnp.asarray(res.solution))).all()
        assert (res.sizes <= 30).all() and (res.sizes > 0).all()


def test_adaptive_needs_fewer_policy_evals():
    """§4.5.1's whole point: top-d selection cuts policy evaluations."""
    adj = random_graph_batch("er", 60, 2, seed=1, rho=0.15)
    params = init_policy(jax.random.key(1), PolicyConfig(embed_dim=8))
    r1 = solve(params, adj, num_layers=2, multi_node=False)
    r8 = solve(params, adj, num_layers=2, multi_node=True)
    assert r8.policy_evals < r1.policy_evals
    # quality within the paper's observed ~1.01x band (untrained: loose 1.35x)
    assert r8.sizes.mean() <= r1.sizes.mean() * 1.35


def test_greedy_and_matching_are_covers():
    for seed in range(3):
        a = random_graph_batch("er", 25, 1, seed=seed, rho=0.25)[0]
        for sol in (greedy_mvc(a), matching_2approx(a)):
            keep = ~sol
            assert a[np.ix_(keep, keep)].sum() == 0


def _greedy_mvc_loop(a):
    """Reference max-degree greedy: fresh row sums every round."""
    a = np.asarray(a, np.float32).copy()
    sol = np.zeros(a.shape[0], bool)
    while a.sum() > 0:
        v = int(a.sum(-1).argmax())
        sol[v] = True
        a[v, :] = 0
        a[:, v] = 0
    return sol


@pytest.mark.parametrize("kind,kw", [("er", {"rho": 0.2}), ("ba", {"d": 3})])
def test_greedy_mvc_batch_matches_row_sum_loop(kind, kw):
    """The incremental-degree batch greedy picks the same covers as the
    loop that recomputes every degree each round, per graph."""
    adj = random_graph_batch(kind, 40, 4, seed=5, **kw)
    adj[2] = 0.0                                 # an edgeless graph
    want = np.stack([_greedy_mvc_loop(a) for a in adj])
    np.testing.assert_array_equal(greedy_mvc_batch(adj), want)


def test_exact_mvc_tiny():
    # triangle: MVC = 2
    a = np.zeros((3, 3), np.float32)
    for u, v in [(0, 1), (1, 2), (0, 2)]:
        a[u, v] = a[v, u] = 1
    assert exact_mvc_size(a) == 2
    # star: MVC = 1
    a = np.zeros((5, 5), np.float32)
    a[0, 1:] = a[1:, 0] = 1
    assert exact_mvc_size(a) == 1


def test_exact_vs_bounds():
    for seed in range(4):
        a = random_graph_batch("er", 16, 1, seed=seed, rho=0.3)[0]
        opt = exact_mvc_size(a)
        assert mvc_lower_bound(a) <= opt <= greedy_mvc(a).sum()
        assert opt <= matching_2approx(a).sum() <= 2 * opt


def test_reference_sizes_heterogeneous_batches():
    """reference_sizes accepts ragged graph lists (mixed node counts) on
    both the exact and the batched-LB fallback path, matching the
    per-graph answers."""
    graphs = [random_graph_batch("er", n, 1, seed=n, rho=0.3)[0]
              for n in (10, 14, 18)]
    assert reference_sizes(graphs).tolist() \
        == [exact_mvc_size(a) for a in graphs]
    lbs = reference_sizes(graphs, exact_limit=5)
    assert lbs.tolist() == [max(mvc_lower_bound(a), 1) for a in graphs]


def test_train_agent_smoke_and_learning_signal():
    """A short run must execute end-to-end; ratio stays in a sane band and
    solutions remain valid covers (full Fig-6 reproduction lives in
    benchmarks/learning_speed.py)."""
    n = 16
    train = random_graph_batch("er", n, 6, seed=0, rho=0.25)
    test = random_graph_batch("er", n, 4, seed=100, rho=0.25)
    refs = reference_sizes(test, exact_limit=20)
    cfg = PolicyConfig(embed_dim=8, num_layers=2, minibatch=8,
                       replay_capacity=512, learning_rate=1e-3,
                       eps_decay_steps=60)
    agent = Agent(cfg, num_nodes=n)
    ratios = []
    log = train_agent(agent, train, episodes=8, tau=2, eval_every=20,
                      eval_fn=lambda ag: ratios.append(
                          evaluate_quality(ag, test, refs)) or ratios[-1],
                      max_steps=80, seed=0)
    assert len(log.losses) > 0 and np.isfinite(log.losses[-1])
    assert len(ratios) >= 1
    assert all(1.0 <= r <= 2.5 for r in ratios)
