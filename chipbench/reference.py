"""Plain reference of what a solve cell's timed path computes.

structure2vec (paper Eq. 1, Alg. 2), the Q head (Eq. 2, Alg. 3), the
adaptive top-d schedule (paper §4.5.1) and the minimum-vertex-cover commit,
in plain ``jax.numpy`` on one graph.  It imports nothing of the program and
takes nothing the program made: the weights and the graph are the
benchmark's own (``graphs.py``), and the program contributes only its
covers: the answer and the covers after each policy evaluation.

``check`` reads a solve the way a served model's tokens are read against
a reference: along the program's own trajectory of covers, one policy
evaluation at a time, the reference scores the candidates, and the nodes
the program committed must be ones the reference ranks in its top d, but
for rounding-sized ties.  The widest gap of a committed node below the
reference's d-th best, as a share of the step's largest score magnitude,
is ``pick_gap`` (the weights keep the graph-level term of the scores at a
node's size, see ``graphs.policy_weights``).  Commits that break the
schedule or the candidate rule, and a solve that stops early or late, are
counted in ``mismatch``.

``precision`` selects how the reference multiplies: ``"highest"`` (float32
at HIGHEST, the reference itself), ``"high"`` (float32 as three bfloat16
passes, the next precision below the configuration's float32 and the
control), ``"bf16"`` (one bfloat16 pass).  The lower passes are emulated
with explicit bfloat16 splits of each operand, multiplied exactly, so they
read the same on any backend.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "high", "bf16")
_HI = lax.Precision.HIGHEST


def _bf16(x):
    # rounding to bfloat16 kept in float32: reduce_precision, unlike a
    # convert round trip, is never dropped by XLA's excess-precision rules
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def mm(spec: str, a, b, precision: str):
    """einsum ``spec`` of float32 operands at the given precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    out = jnp.einsum(spec, a_hi, b_hi, precision=_HI)
    if precision == "high":
        out = out + jnp.einsum(spec, a_hi, b_lo, precision=_HI)
        out = out + jnp.einsum(spec, a_lo, b_hi, precision=_HI)
    elif precision != "bf16":
        raise ValueError(f"unknown precision {precision!r}")
    return out


class Dense(NamedTuple):
    """One graph as its (N, N) 0/1 float32 adjacency."""
    adj: jax.Array

    @property
    def n(self):
        return self.adj.shape[-1]

    def degree(self, keep):
        return jnp.einsum("ln,n->l", self.adj, keep, precision=_HI) * keep

    def aggregate(self, x, keep, precision):
        # neighbour sums over the residual graph A * keep keep^T
        return mm("kl,ln->kn", x * keep, self.adj, precision) * keep

    def edges(self, keep):
        return self.degree(keep).sum()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Csr:
    """One graph as sorted directed edges (rows, cols, mask), in chunks of
    equal length: (C, T) arrays, padding rows 0 and cols N with mask 0."""
    rows: jax.Array
    cols: jax.Array
    mask: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))

    def _weights(self, keep):
        kp = jnp.concatenate([keep, jnp.zeros((1,), keep.dtype)])
        return self.mask * keep[self.rows] * kp[self.cols]

    def degree(self, keep):
        w = self._weights(keep)
        return jax.ops.segment_sum(w.reshape(-1), self.rows.reshape(-1),
                                   num_segments=self.n)

    def aggregate(self, x, keep, precision):
        del precision                 # sums of products by 0/1 weights
        w = self._weights(keep)
        xt = jnp.concatenate([x.T, jnp.zeros((1, x.shape[0]), x.dtype)])

        def chunk(acc, part):
            r, c, wc = part
            return acc + jax.ops.segment_sum(xt[c] * wc[:, None], r,
                                             num_segments=self.n), None

        acc0 = jnp.zeros((self.n, x.shape[0]), jnp.float32)
        acc, _ = lax.scan(chunk, acc0, (self.rows, self.cols, w))
        return acc.T

    def edges(self, keep):
        return self._weights(keep).sum()


def csr_from_arrays(indptr, indices, mask, chunk: int = 1 << 22) -> Csr:
    """A :class:`Csr` from CSR arrays (host numpy), rows derived from
    ``indptr`` and padded to whole chunks."""
    n = len(indptr) - 1
    e = len(indices)
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    rows = np.concatenate([rows, np.zeros(e - len(rows), np.int32)])
    t = min(chunk, e)
    c = -(-e // t)
    pad = c * t - e
    rows = np.concatenate([rows, np.zeros(pad, np.int32)]).reshape(c, t)
    cols = np.concatenate([indices, np.full(pad, n, np.int32)]).reshape(c, t)
    m = np.concatenate([mask, np.zeros(pad, bool)]).reshape(c, t)
    return Csr(jnp.asarray(rows), jnp.asarray(cols),
               jnp.asarray(m, jnp.float32), n)


def scores(w: dict, graph, sol, cand, *, num_layers: int, precision: str):
    """(N,) Q scores of the candidates (-inf elsewhere) on the residual
    graph that the partial cover ``sol`` leaves."""
    keep = 1.0 - sol
    deg = graph.degree(keep)
    embed1 = w["theta1"][:, None] * sol[None, :]
    h = jax.nn.relu(w["theta2"][:, None] * deg[None, :])
    base = embed1 + mm("kj,jn->kn", w["theta3"], h, precision)
    e = jnp.zeros_like(base)
    for _ in range(num_layers):
        nbr = graph.aggregate(e, keep, precision)
        e = jax.nn.relu(base + mm("kj,jn->kn", w["theta4"], nbr, precision))
    pooled = mm("kj,j->k", w["theta5"], e.sum(-1), precision)
    local = mm("kj,jn->kn", w["theta6"], e * cand[None, :], precision)
    both = jax.nn.relu(jnp.concatenate(
        [jnp.broadcast_to(pooled[:, None], local.shape), local]))
    q = mm("c,cn->n", w["theta7"], both, precision)
    return jnp.where(cand > 0.5, q, -jnp.inf)


def adaptive_d(num_candidates, n: int, max_d: int):
    """Commits per evaluation (paper §4.5.1): max_d while more than half
    the nodes are candidates, then max_d/2, /4, /8, each at least 1."""
    c = num_candidates
    return jnp.where(c > n / 2, max_d,
           jnp.where(c > n / 4, max(max_d // 2, 1),
           jnp.where(c > n / 8, max(max_d // 4, 1),
                     max(max_d // 8, 1))))


class Reading(NamedTuple):
    pick_gap: float        # widest gap of the program's picks below the
                           # reference's d-th best, per unit of score
    mismatch: int          # picks, steps and answers that break the rules
    control_gap: float     # the same gap of the control's own picks


@functools.partial(jax.jit, static_argnames=(
    "num_layers", "max_d", "adaptive", "control"))
def _step(w, graph, before, after, *, num_layers: int, max_d: int,
          adaptive: bool, control: Optional[str]):
    """One policy evaluation of the program, from cover ``before`` to
    cover ``after``, against the reference."""
    n = graph.n
    width = min(max_d, n)
    rank = jnp.arange(width)
    keep = 1.0 - before
    cand = ((graph.degree(keep) > 0) & (before < 0.5)).astype(jnp.float32)
    ncand = cand.sum().astype(jnp.int32)
    d = adaptive_d(ncand, n, max_d) if adaptive else 1
    dv = jnp.minimum(d, ncand)
    ref = scores(w, graph, before, cand, num_layers=num_layers,
                 precision="highest")
    top, _ = lax.top_k(ref, width)
    ref_dth = top[jnp.maximum(dv - 1, 0)]
    scale = jnp.max(jnp.where(cand > 0.5, jnp.abs(ref), 0.0))
    scale = jnp.where(scale > 0, scale, 1.0)
    picked = (after > 0.5) & (before < 0.5)
    ok = picked & (cand > 0.5)
    low = jnp.min(jnp.where(ok, ref, jnp.inf))
    gap = jnp.where(ok.any(), (ref_dth - low) / scale, 0.0)
    mismatch = (jnp.abs(picked.sum() - dv) + (picked & (cand < 0.5)).sum()
                + ((before > 0.5) & (after < 0.5)).sum())
    cgap = jnp.float32(0)
    if control is not None:
        ctl = scores(w, graph, before, cand, num_layers=num_layers,
                     precision=control)
        _, ci = lax.top_k(ctl, width)
        clow = jnp.min(jnp.where(rank < dv, ref[ci], jnp.inf))
        cgap = jnp.where(dv > 0, (ref_dth - clow) / scale, 0.0)
    return gap, mismatch, cgap, graph.edges(keep)


def check(w: dict, graph, covers, *, num_layers: int, max_d: int,
          adaptive: bool, finished: bool,
          control: Optional[str] = None) -> Reading:
    """Check the program's solve step by step.

    ``covers`` are the program's covers S(0) = {}, S(1), ..., S(T) after
    each of its T policy evaluations; ``finished`` says whether the program
    stopped because no edge was left (and not at its cap).  At each step
    the reference scores the candidates of S(t-1); the program's picks
    S(t) - S(t-1) must be as many as the adaptive schedule allows, all
    candidates, and score no lower than the reference's d-th best but for
    rounding.  The residual graph must be non-empty before every step, and
    empty after the last one exactly when ``finished``."""
    gap = cgap = 0.0
    mismatch = 0
    left = None
    for before, after in zip(covers[:-1], covers[1:]):
        g, m, c, left = _step(
            w, graph, jnp.asarray(before, jnp.float32),
            jnp.asarray(after, jnp.float32), num_layers=num_layers,
            max_d=max_d, adaptive=adaptive, control=control)
        gap, cgap = max(gap, float(g)), max(cgap, float(c))
        mismatch += int(m) + int(float(left) == 0)
    end = float(graph.edges(1.0 - jnp.asarray(covers[-1], jnp.float32)))
    mismatch += int(finished != (end == 0))
    return Reading(pick_gap=gap, mismatch=mismatch, control_gap=cgap)
