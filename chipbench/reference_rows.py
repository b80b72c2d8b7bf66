"""The plain reference of ``reference.py`` on a dense graph whose rows are
split over devices, for cells whose graph no single device can hold.

``DenseRows`` stands where ``reference.Dense`` stands in
``reference.check`` and ``reference.scores``: the same degree and
neighbour aggregation over keep-masks, in plain ``jax.numpy`` at HIGHEST
precision, with no residual rewrite.  It imports nothing of the program.
It departs from ``Dense`` in these ways only:

- it holds the (1, N, N) adjacency as made, split by rows over the
  devices, and every product runs under ``jit`` on that array, so the
  compiler partitions it by rows: each device multiplies its own rows and
  the (K, N) partial sums are added across devices.  No device holds the
  whole graph, and the aggregation at HIGHEST sums in another order than
  one device does, a difference of rounding only;
- for the lower-precision controls it splits only the embedding into
  bfloat16 parts: a 0/1 adjacency is exact in bfloat16, so the adjacency's
  low part that ``reference.mm`` multiplies is zero, and leaving it out
  gives the same numbers without two more N² arrays on each device.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import _split

_HI = lax.Precision.HIGHEST


@jax.jit
def _degree(adj, keep):
    return jnp.einsum("bln,n->l", adj, keep, precision=_HI) * keep


@functools.partial(jax.jit, static_argnames=("precision",))
def _aggregate(adj, x, keep, precision: str):
    a = x * keep
    if precision == "highest":
        out = jnp.einsum("kl,bln->kn", a, adj, precision=_HI)
    else:
        a_hi, a_lo = _split(a)
        out = jnp.einsum("kl,bln->kn", a_hi, adj, precision=_HI)
        if precision == "high":
            out = out + jnp.einsum("kl,bln->kn", a_lo, adj, precision=_HI)
        elif precision != "bf16":
            raise ValueError(f"unknown precision {precision!r}")
    return out * keep


class DenseRows(NamedTuple):
    """One graph as its (1, N, N) 0/1 float32 adjacency, rows split over
    devices."""
    adj: jax.Array

    @property
    def n(self):
        return self.adj.shape[-1]

    def degree(self, keep):
        return _degree(self.adj, keep)

    def aggregate(self, x, keep, precision):
        # neighbour sums over the residual graph A * keep keep^T
        return _aggregate(self.adj, x, keep, precision)

    def edges(self, keep):
        return self.degree(keep).sum()
