"""The inputs made from the seed are the same for the same seed, and are
the graphs and weights they claim to be."""
import numpy as np

from chipbench import graphs


def test_dense_er_is_deterministic_symmetric_and_of_its_density():
    big = 2 ** 31 + 12345
    a = np.asarray(graphs.dense_er(400, 0.15, big))
    assert np.array_equal(a, np.asarray(graphs.dense_er(400, 0.15, big)))
    assert not np.array_equal(a, np.asarray(graphs.dense_er(400, 0.15, 1)))
    assert not np.array_equal(
        a, np.asarray(graphs.dense_er(400, 0.15, big + 2 ** 32)))
    a = a[0]
    assert a.dtype == np.float32 and set(np.unique(a)) <= {0.0, 1.0}
    assert np.array_equal(a, a.T) and not a.diagonal().any()
    pairs = 400 * 399 / 2
    sigma = np.sqrt(pairs * 0.15 * 0.85)
    assert abs(np.triu(a, 1).sum() - 0.15 * pairs) < 4 * sigma


def test_dense_er_blocks_do_not_change_the_graph():
    one = np.asarray(graphs.dense_er(300, 0.2, 9, block=300))
    many = np.asarray(graphs.dense_er(300, 0.2, 9, block=64))
    assert np.array_equal(one, many)


def test_ba_csr_is_deterministic_and_a_simple_undirected_graph():
    n, d = 3000, 4
    indptr, indices, mask = graphs.ba_csr(n, d, 2 ** 31 + 5)
    again = graphs.ba_csr(n, d, 2 ** 31 + 5)
    for x, y in zip((indptr, indices, mask), again):
        assert np.array_equal(x, y)
    assert len(indices) == graphs.ba_capacity(n, d) == 2 * (n * d - 10)
    e = int(mask.sum())
    assert indptr[-1] == e and mask[:e].all() and not mask[e:].any()
    assert (indices[e:] == n).all()
    rows = np.repeat(np.arange(n), np.diff(indptr))
    cols = indices[:e]
    assert (rows != cols).all()
    key = rows.astype(np.int64) * n + cols
    assert (np.diff(key) > 0).all()               # sorted, no repeats
    assert np.array_equal(np.sort(cols.astype(np.int64) * n + rows), key)
    assert np.diff(indptr).min() >= 1


def test_ba_csr_matches_the_program_generator():
    from chipbench.harness import import_program
    g = import_program("repro.core.graphs")
    src, dst = g.barabasi_albert_edges(5000, 10, seed=3)
    want_ptr, want_idx = g.csr_from_edges(5000, src, dst)
    indptr, indices, mask = graphs.ba_csr(5000, 10, 3)
    assert np.array_equal(indptr, want_ptr)
    assert np.array_equal(indices[:mask.sum()], want_idx)


def test_policy_weights_follow_the_program_init():
    from chipbench.harness import import_program
    import jax
    policy = import_program("repro.core.policy")
    seed = 2 ** 31 + 99
    w = graphs.policy_weights(seed, 32)
    again = graphs.policy_weights(seed, 32)
    p = policy.init_policy(graphs.seed_key(seed), policy.PolicyConfig())
    want = {**{f"theta{i}": getattr(p.em, f"theta{i}") for i in (1, 2, 3, 4)},
            **{f"theta{i}": getattr(p.q, f"theta{i}") for i in (5, 6, 7)}}
    for k, v in want.items():
        assert np.array_equal(np.asarray(w[k]), np.asarray(again[k]))
        # one jitted draw against the program's eager one: equal to rounding
        np.testing.assert_allclose(np.asarray(w[k]), np.asarray(v),
                                   rtol=1e-6, atol=1e-7)
    assert jax.tree.leaves(w)[0].dtype == np.float32
