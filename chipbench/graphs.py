"""Traffic inputs made from the seed: graphs and policy weights.

Kept with the benchmark so that no change to the program moves them.

- ``dense_er``: Erdős–Rényi G(N, rho) as a dense (1, N, N) float32
  adjacency, made on the device in one jitted call.  Edge (i, j) is present
  when a counter hash of (min(i, j), max(i, j), seed) falls under rho, so
  the matrix is symmetric by construction and is built in row blocks: the
  peak is the adjacency itself plus one block, never a second N² array.
- ``ba_csr``: Barabási–Albert BA(N, d) as CSR arrays, made on the host by
  the vectorized Batagelj–Brandes copy model (a copy of the streaming
  generator in the program's ``core/graphs.py``), padded to a capacity that
  depends on N and d only, so every seed runs the same compiled shapes.
- ``policy_weights``: the structure2vec and Q-head parameters, drawn as the
  program's ``init_policy(jax.random.key(seed), cfg)`` draws them, in one
  jitted call on the device, with the graph-level weight theta5 scaled to
  the graph (see the function).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 bits of a larger seed without x64)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit finalizer: a bijective avalanche mix."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    return x ^ (x >> 16)


def _seed_words(seed: int):
    return (np.uint32(seed & 0xFFFFFFFF),
            np.uint32((seed >> 32) & 0xFFFFFFFF) ^ np.uint32(0x9E3779B9))


@functools.partial(jax.jit, static_argnames=("n", "rho", "block"))
def _dense_er(s1, s2, *, n: int, rho: float, block: int):
    threshold = jnp.uint32(min(int(rho * 2.0 ** 32), 2 ** 32 - 1))
    cols = jnp.arange(n, dtype=jnp.uint32)
    blocks = -(-n // block)

    def fill(i, adj):
        r0 = jnp.minimum(i * block, n - block)
        rows = (r0 + jnp.arange(block)).astype(jnp.uint32)
        lo = jnp.minimum(rows[:, None], cols[None, :])
        hi = jnp.maximum(rows[:, None], cols[None, :])
        h = _fmix32(_fmix32((lo * jnp.uint32(n) + hi) ^ s1) + s2)
        edge = (h < threshold) & (lo != hi)
        return jax.lax.dynamic_update_slice(
            adj, edge.astype(jnp.float32)[None], (0, r0, 0))

    adj = jnp.zeros((1, n, n), jnp.float32)
    return jax.lax.fori_loop(0, blocks, fill, adj)


def dense_er(n: int, rho: float, seed: int, *, block: int = 512) -> jax.Array:
    """(1, N, N) float32 ER(N, rho) adjacency on the default device."""
    if n * n >= 2 ** 32:
        raise ValueError(f"dense_er hashes pair ids in 32 bits; N={n} is "
                         f"too large")
    s1, s2 = _seed_words(seed)
    return _dense_er(s1, s2, n=n, rho=float(rho), block=min(block, n))


def ba_edges(n: int, d: int, seed: int):
    """BA(n, d) as a directed edge list (src, dst), O(E) time and memory:
    edge t's target is a uniform draw from the 2t endpoints of earlier
    edges, odd draws resolved by pointer chasing (copy of the program's
    ``barabasi_albert_edges``)."""
    rng = np.random.default_rng(seed)
    m = np.minimum(np.arange(n, dtype=np.int64), d)
    src = np.repeat(np.arange(n, dtype=np.int64), m)
    t = np.arange(len(src), dtype=np.int64)
    if len(t) == 0:
        return src, src.copy()
    r = rng.integers(0, np.maximum(2 * t, 1))
    rr = r.copy()
    odd = (rr & 1) == 1
    while odd.any():
        rr[odd] = r[(rr[odd] - 1) >> 1]
        odd = (rr & 1) == 1
    dst = src[rr >> 1]
    dst[0] = 0
    return src, dst


def ba_capacity(n: int, d: int) -> int:
    """Directed edge slots of BA(n, d) before self-loops and repeats are
    dropped: every added node brings min(v, d) edges, each stored twice."""
    return 2 * int(np.minimum(np.arange(n, dtype=np.int64), d).sum())


def ba_csr(n: int, d: int, seed: int):
    """BA(n, d) as (indptr (N+1,) int32, indices (cap,) int32, mask (cap,)
    bool): symmetrized, self-loops and repeats dropped, rows sorted, padded
    with the sentinel column N to :func:`ba_capacity` slots."""
    src, dst = ba_edges(n, d, seed)
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    key = np.unique(src[keep] * np.int64(n) + dst[keep])
    src, dst = key // n, key % n
    indptr = np.zeros((n + 1,), np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    cap = ba_capacity(n, d)
    indices = np.full((cap,), n, np.int32)
    indices[:len(dst)] = dst
    mask = np.zeros((cap,), bool)
    mask[:len(dst)] = True
    return indptr.astype(np.int32), indices, mask


@functools.partial(jax.jit, static_argnames=("k",))
def _weights(key, *, k: int):
    scale = 0.1
    k_em, k_q = jax.random.split(key)
    k1, k2, k3, k4 = jax.random.split(k_em, 4)
    em = {"theta1": jax.random.normal(k1, (k,)) * scale,
          "theta2": jax.random.normal(k2, (k,)) * scale,
          "theta3": jax.random.normal(k3, (k, k)) * (scale / jnp.sqrt(k)),
          "theta4": jax.random.normal(k4, (k, k)) * (scale / jnp.sqrt(k))}
    k5, k6, k7 = jax.random.split(k_q, 3)
    s = scale / jnp.sqrt(k)
    q = {"theta5": jax.random.normal(k5, (k, k)) * s,
         "theta6": jax.random.normal(k6, (k, k)) * s,
         "theta7": jax.random.normal(k7, (2 * k,)) * s}
    return {**em, **q}


def policy_weights(seed: int, embed_dim: int, nodes: int = 1) -> dict:
    """theta1..theta7 of structure2vec (paper Eq. 1) and the Q head
    (Eq. 2) as a dict of float32 device arrays, theta5 divided by the
    graph's node count ``nodes``.

    theta5 weighs the graph-level term of the Q head, the same for every
    node, so it moves no node's rank in exact arithmetic.  Drawn as the
    program draws it, that term is the sum over N nodes and outgrows the
    part that ranks them N-fold; its float32 rounding alone then decides
    the top-d picks of a large graph, and no answer could show a change of
    precision.  Divided by N it stays the size of one node's own term.  The
    work of every call is unchanged."""
    w = _weights(seed_key(seed), k=embed_dim)
    w["theta5"] = w["theta5"] / nodes
    return w
