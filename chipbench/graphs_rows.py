"""The dense Erdős–Rényi graph of ``graphs.dense_er``, made by rows on the
devices of a mesh: each device makes only its own N/P rows, so no device
ever holds the whole (N, N) adjacency.

Edge (i, j) is present when the counter hash of (min(i, j), max(i, j),
seed) that ``graphs.dense_er`` uses falls under rho; the hash depends on
the pair alone, so the rows are bit for bit the rows of
``graphs.dense_er(n, rho, seed)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from chipbench.graphs import _fmix32, _seed_words


@functools.partial(jax.jit, static_argnames=("n", "rho", "block", "mesh",
                                             "axis"))
def _rows(s1, s2, *, n: int, rho: float, block: int, mesh, axis: str):
    nl = n // mesh.shape[axis]
    threshold = jnp.uint32(min(int(rho * 2.0 ** 32), 2 ** 32 - 1))
    cols = jnp.arange(n, dtype=jnp.uint32)
    blocks = -(-nl // block)

    def local(s1, s2):
        base = jax.lax.axis_index(axis) * nl

        def fill(i, adj):
            r0 = jnp.minimum(i * block, nl - block)
            rows = (base + r0 + jnp.arange(block)).astype(jnp.uint32)
            lo = jnp.minimum(rows[:, None], cols[None, :])
            hi = jnp.maximum(rows[:, None], cols[None, :])
            h = _fmix32(_fmix32((lo * jnp.uint32(n) + hi) ^ s1) + s2)
            edge = (h < threshold) & (lo != hi)
            return jax.lax.dynamic_update_slice(
                adj, edge.astype(jnp.float32)[None], (0, r0, 0))

        adj = jnp.zeros((1, nl, n), jnp.float32)
        return jax.lax.fori_loop(0, blocks, fill, adj)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(None, axis, None),
                         check_vma=False)(s1, s2)


def dense_er_rows(n: int, rho: float, seed: int, mesh, axis: str, *,
                  block: int = 512) -> jax.Array:
    """(1, N, N) float32 ER(N, rho) adjacency with its rows split evenly
    over ``mesh``'s axis ``axis`` (its other axes hold copies)."""
    if n * n >= 2 ** 32:
        raise ValueError(f"dense_er hashes pair ids in 32 bits; N={n} is "
                         f"too large")
    p = mesh.shape[axis]
    if n % p:
        raise ValueError(f"N={n} rows do not split evenly over {p} devices")
    s1, s2 = _seed_words(seed)
    return _rows(s1, s2, n=n, rho=float(rho), block=min(block, n // p),
                 mesh=mesh, axis=axis)
