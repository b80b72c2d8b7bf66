"""Graph generation and distributed graph containers (paper §4.1).

The paper stores each graph as (A, C, S): adjacency matrix, candidate-node
mask, partial-solution mask — spatially partitioned row-wise across P devices.
This module holds BOTH on-device representations behind which every layer of
the stack dispatches (DESIGN.md §1):

- dense ``GraphState``: (B, N, N) residual adjacency blocks (MXU-friendly),
  rewritten after every commit;
- sparse ``SparseGraphState``: padded neighbor lists (B, N, D) + validity
  masks — the paper's distributed sparse storage (§5.2) made TPU-gatherable.
  The topology is NEVER rewritten; residual edges are derived from the
  partial-solution mask via :func:`residual_edge_mask`.
- csr ``CsrGraphState``: flat CSR arrays ``(indptr, indices, edge_mask)``
  (DESIGN.md §13) — edge-proportional storage with NO per-node padding, so
  one hub node no longer costs hub-degree padding on every row.  Like the
  sparse rep the topology is immutable; residual edges derive from S via
  :func:`csr_residual_edge_mask`.  This is the rep that reaches the
  paper's N ≥ 1M / 10M+-edge graphs (§6.4).
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Generators (paper §6.1: ER(n, rho=0.15), BA(n, d=4), real-world Facebook
# graphs).  Pure numpy + explicit seeding so training is reproducible.
# ---------------------------------------------------------------------------

def erdos_renyi(n: int, rho: float = 0.15, *, seed: int) -> np.ndarray:
    """ER(n, rho): each unordered pair connected with probability rho."""
    rng = np.random.default_rng(seed)
    upper = rng.random((n, n)) < rho
    upper = np.triu(upper, k=1)
    a = (upper | upper.T).astype(np.float32)
    return a


def barabasi_albert(n: int, d: int = 4, *, seed: int) -> np.ndarray:
    """BA(n, d): preferential attachment, d edges per new node (paper d=4).

    Uses the repeated-endpoints trick: sampling a uniform entry of the edge
    endpoint list IS degree-proportional sampling, so each new node costs
    O(d) instead of the O(n) renormalized ``rng.choice(p=...)`` — the dense
    output assembly is a single vectorized index assignment.
    """
    rng = np.random.default_rng(seed)
    m0 = min(d + 1, n)
    si, sj = np.triu_indices(m0, k=1)
    n_new = max(n - m0, 0)
    # edge endpoint multiset: clique edges + up to d per added node
    cap = 2 * (len(si) + n_new * d)
    endpoints = np.empty((cap,), np.int64)
    cnt = 2 * len(si)
    endpoints[0:cnt:2] = si
    endpoints[1:cnt:2] = sj
    src = np.empty((n_new * d,), np.int64)
    dst = np.empty((n_new * d,), np.int64)
    ecnt = 0
    for v in range(m0, n):
        k = min(d, v)
        chosen: list = []
        seen: set = set()
        while len(chosen) < k:
            draw = endpoints[rng.integers(0, cnt, size=2 * k)]
            for t in draw:
                t = int(t)
                if t not in seen:
                    seen.add(t)
                    chosen.append(t)
                    if len(chosen) == k:
                        break
        targets = np.asarray(chosen, np.int64)
        src[ecnt:ecnt + k] = v
        dst[ecnt:ecnt + k] = targets
        endpoints[cnt:cnt + k] = v
        endpoints[cnt + k:cnt + 2 * k] = targets
        cnt += 2 * k
        ecnt += k
    a = np.zeros((n, n), dtype=np.float32)
    a[si, sj] = a[sj, si] = 1.0
    a[src[:ecnt], dst[:ecnt]] = a[dst[:ecnt], src[:ecnt]] = 1.0
    return a


def social_like(n: int, communities: int = 8, p_in: float = 0.08,
                p_out: float = 0.002, *, seed: int) -> np.ndarray:
    """Stochastic-block-model stand-in for the paper's Facebook graphs
    (Vanderbilt/Georgetown/Mississippi are not redistributable offline;
    SBM with strong communities reproduces their low edge probability
    ~0.01 and clustered structure)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, communities, size=n)
    same = labels[:, None] == labels[None, :]
    p = np.where(same, p_in, p_out)
    upper = np.triu(rng.random((n, n)) < p, k=1)
    return (upper | upper.T).astype(np.float32)


def random_graph_batch(kind: str, n: int, batch: int, *, seed: int,
                       **kw) -> np.ndarray:
    gen = {"er": erdos_renyi, "ba": barabasi_albert, "social": social_like}[kind]
    return np.stack([gen(n, seed=seed + i, **kw) for i in range(batch)])


def edge_count(a: np.ndarray) -> int:
    return int(a.sum() / 2)


# ---------------------------------------------------------------------------
# Dense graph state (B graphs stacked; paper Fig 2).
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphState:
    """State of a batch of B graphs with N nodes each.

    adj:       (B, N, N) float — residual adjacency (edges already covered by
               the partial solution are zeroed, paper Fig 4 right panel).
    candidate: (B, N) float mask — the paper's C vector.
    solution:  (B, N) float mask — the paper's S vector.
    row_axis:  None, or the ``shard_map`` axis over which ``adj``'s rows
               are split (paper §5.3 distributed storage): ``adj`` is then
               this device's (B, N/P, N) row block, while ``candidate`` and
               ``solution`` stay whole.  The methods below are every
               adjacency access of the env commit rules; with a row axis
               each works on the resident rows and exchanges (B, N)
               vectors, so no device ever holds another's rows.
    """
    adj: jax.Array
    candidate: jax.Array
    solution: jax.Array
    row_axis: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def batch(self) -> int:
        return self.adj.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.adj.shape[-1]

    def local_rows(self, v: jax.Array) -> jax.Array:
        """The resident rows' entries of a whole (B, N) vector."""
        if self.row_axis is None:
            return v
        nl = self.adj.shape[1]
        return jax.lax.dynamic_slice_in_dim(
            v, jax.lax.axis_index(self.row_axis) * nl, nl, axis=1)

    def all_rows(self, x: jax.Array) -> jax.Array:
        """The whole (B, N) vector from each device's (B, N/P) rows."""
        if self.row_axis is None:
            return x
        with jax.named_scope("mesh.collective"):
            return jax.lax.all_gather(x, self.row_axis, axis=1, tiled=True)

    def degree(self) -> jax.Array:
        """(B, N) row sums of the adjacency."""
        return self.all_rows(self.adj.sum(-1))

    def matvec(self, v: jax.Array) -> jax.Array:
        """(B, N) product of the adjacency with a whole (B, N) vector."""
        return self.all_rows(jnp.einsum("bnm,bm->bn", self.adj, v))

    def masked(self, keep: jax.Array) -> jax.Array:
        """The adjacency with the rows and columns where ``keep`` is 0
        zeroed: A * keep keepᵀ (only the resident rows)."""
        return (self.adj * self.local_rows(keep)[:, :, None]
                * keep[:, None, :])

    def total(self) -> jax.Array:
        """(B,) sum of every adjacency entry (twice the edge count)."""
        t = self.adj.sum((-1, -2))
        if self.row_axis is None:
            return t
        with jax.named_scope("mesh.collective"):
            return jax.lax.psum(t, self.row_axis)

    def closed_keep(self, sel: jax.Array) -> jax.Array:
        """Keep factors (B, N) after removing ``sel`` and its neighbours
        (:func:`closed_neighborhood_keep_dense` on this state)."""
        nbr_s = self.matvec(sel)
        return (1.0 - sel) * (1.0 - (nbr_s > 0).astype(jnp.float32))


def init_state(adj: jax.Array) -> GraphState:
    """Fresh state: empty solution; candidates = nodes with degree > 0."""
    adj = jnp.asarray(adj, jnp.float32)
    if adj.ndim == 2:
        adj = adj[None]
    deg = adj.sum(-1)
    return GraphState(
        adj=adj,
        candidate=(deg > 0).astype(jnp.float32),
        solution=jnp.zeros(adj.shape[:2], jnp.float32),
    )


def residual_adjacency(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """Tuples2Graphs (paper Alg 5 line 21): rebuild the residual subgraph from
    the *original* adjacency and a partial-solution mask.  Removing a node
    zeroes its row and column, i.e. A ⊙ (1-S)(1-S)ᵀ."""
    keep = 1.0 - solution
    return adj0 * keep[..., :, None] * keep[..., None, :]


# ---------------------------------------------------------------------------
# Sparse graph state: padded neighbor lists + masks (paper §4.1/§5.2).
# The topology (neighbors, valid) is immutable; (candidate, solution) evolve.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseGraphState:
    """Sparse counterpart of :class:`GraphState` (DESIGN.md §1).

    neighbors: (B, N, D) int32 padded neighbor ids, sentinel N for padding.
    valid:     (B, N, D) bool — static topology mask (never rewritten).
    candidate: (B, N) float mask — the paper's C vector.
    solution:  (B, N) float mask — the paper's S vector.

    A residual edge (u, v) exists iff the original edge exists and neither
    endpoint is in the solution — derived on the fly, O(N·D) state instead
    of O(N²).

    ``residual`` (static) records the env's topology mode (``env.register``):
    ``True``/"solution" — the policy sees the residual subgraph implied by
    S (MVC semantics, the dense path's rewritten adjacency);
    ``False``/"none" — the original topology (MaxCut/MDS: selecting a node
    deletes no edges); ``"closed"`` — S and its neighbors removed (MIS).
    The sparse scorer derives matching edge factors
    (``s2v_sparse.edge_factors``).
    """
    neighbors: jax.Array
    valid: jax.Array
    candidate: jax.Array
    solution: jax.Array
    residual: bool = dataclasses.field(default=True,
                                       metadata=dict(static=True))

    @property
    def batch(self) -> int:
        return self.neighbors.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.neighbors.shape[1]

    @property
    def max_degree(self) -> int:
        return self.neighbors.shape[2]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseGraphBatch:
    """Static topology for B graphs: neighbors (B, N, D) int32 padded with
    N (a sentinel; embeddings are padded with a zero column), valid
    (B, N, D) bool.  Used both as the batch topology inside
    ``SparseGraphState`` construction and as the training-dataset container
    (G graphs indexed by the replay buffer's graph ids).  Registered as a
    pytree so the fused train step can take it as its dataset operand."""
    neighbors: jax.Array
    valid: jax.Array

    @property
    def batch(self):
        return self.neighbors.shape[0]

    @property
    def num_nodes(self):
        return self.neighbors.shape[1]

    @property
    def max_degree(self):
        return self.neighbors.shape[2]


def residual_edge_mask(neighbors: jax.Array, valid: jax.Array,
                       solution: jax.Array) -> jax.Array:
    """(B, N, D) float residual-edge factors: valid ∧ keep[u] ∧ keep[v].

    This is the sparse analogue of :func:`residual_adjacency` — instead of
    rewriting storage it derives the residual subgraph from the immutable
    topology and the current partial-solution mask."""
    keep = 1.0 - solution
    keep_pad = jnp.pad(keep, ((0, 0), (0, 1)))              # sentinel slot
    keep_nbr = jax.vmap(lambda kb, nb: kb[nb])(keep_pad, neighbors)
    return valid.astype(jnp.float32) * keep_nbr * keep[:, :, None]


def closed_neighborhood_keep(neighbors: jax.Array, valid: jax.Array,
                             solution: jax.Array) -> jax.Array:
    """(B, N) keep factors for CLOSED-neighborhood removal: a node survives
    iff it is neither in ``solution`` nor adjacent to it (MIS residual
    semantics — committing a node removes it and its neighbors).  The
    sparse analogue of zeroing the rows/columns of S ∪ N(S)."""
    sol_pad = jnp.pad(solution, ((0, 0), (0, 1)))           # sentinel slot
    s_nbr = jax.vmap(lambda sb, nb: sb[nb])(sol_pad, neighbors)
    any_nbr = (valid.astype(jnp.float32) * s_nbr).max(-1)
    return (1.0 - solution) * (1.0 - any_nbr)


def closed_neighborhood_keep_dense(adj: jax.Array,
                                   solution: jax.Array) -> jax.Array:
    """Dense counterpart of :func:`closed_neighborhood_keep`: keep factors
    over a (B, N, N) adjacency — works on the original topology (replay
    re-materialization) and on a residual adjacency (incremental commits:
    a neighbor already removed has no surviving edge to lose)."""
    nbr_s = jnp.einsum("bnm,bm->bn", adj, solution)
    return (1.0 - solution) * (1.0 - (nbr_s > 0).astype(jnp.float32))


def sparse_batch_from_dense(adj: np.ndarray,
                            max_degree: Optional[int] = None
                            ) -> SparseGraphBatch:
    """adj (B, N, N) → padded edge lists with a common max degree
    (vectorized: one ``np.nonzero`` + cumcount, no per-node loop).

    ``max_degree`` of None or 0 derives the width from the batch; an
    explicit value below the true max degree raises rather than silently
    dropping edges (which would corrupt residual degrees and candidates).
    """
    adj = np.asarray(adj)
    if adj.ndim == 2:
        adj = adj[None]
    b, n, _ = adj.shape
    deg = (adj > 0).sum(-1)
    true_md = int(deg.max()) if deg.size else 0
    if not max_degree:                       # None or 0 → derive
        md = max(true_md, 1)
    elif max_degree < true_md:
        raise ValueError(
            f"max_degree={max_degree} is below the batch's true max degree "
            f"{true_md}; refusing to silently drop edges")
    else:
        md = max_degree
    nbrs = np.full((b, n, md), n, np.int32)
    val = np.zeros((b, n, md), bool)
    bi, rows, cols = np.nonzero(adj > 0)
    flat = bi * n + rows
    counts = np.bincount(flat, minlength=b * n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    offs = np.arange(len(flat)) - starts[flat]
    keep = offs < md
    nbrs[bi[keep], rows[keep], offs[keep]] = cols[keep]
    val[bi[keep], rows[keep], offs[keep]] = True
    return SparseGraphBatch(neighbors=jnp.asarray(nbrs),
                            valid=jnp.asarray(val))


def sparse_init_state(g: SparseGraphBatch) -> SparseGraphState:
    """Fresh sparse state: empty solution; candidates = degree > 0."""
    deg = g.valid.sum(-1)
    return SparseGraphState(
        neighbors=g.neighbors, valid=g.valid,
        candidate=(deg > 0).astype(jnp.float32),
        solution=jnp.zeros(g.neighbors.shape[:2], jnp.float32),
    )


# ---------------------------------------------------------------------------
# CSR graph state: flat compressed-sparse-row arrays (DESIGN.md §13).
# The first representation whose storage is EDGE-proportional — no (N, N)
# dense block and no per-node max-degree padding, so a power-law hub costs
# only its own edges.  Topology (indptr, indices, edge_mask) is immutable;
# residual edges derive from the solution mask exactly like the sparse rep.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CsrGraphBatch:
    """Static CSR topology for B graphs with a common (N, E) shape.

    indptr:    (B, N+1) int32 — row j's directed edges live in
               ``indices[indptr[j]:indptr[j+1]]``; ``indptr[N]`` is the
               graph's true directed edge count (≤ E).
    indices:   (B, E) int32 column ids, padded with the sentinel N past the
               true edge count (embeddings pad a zero column, so sentinel
               gathers are inert — same convention as ``SparseGraphBatch``).
    edge_mask: (B, E) bool — True on real edges, False on padding.

    Graphs are undirected: every edge appears twice (u→v and v→u), matching
    the dense adjacency's symmetry.  Registered as a pytree so the fused
    train step can take it as its dataset operand.
    """
    indptr: jax.Array
    indices: jax.Array
    edge_mask: jax.Array

    @property
    def batch(self) -> int:
        return self.indptr.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.indptr.shape[1] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CsrGraphState:
    """CSR counterpart of :class:`GraphState` / :class:`SparseGraphState`.

    Topology fields as in :class:`CsrGraphBatch`; (candidate, solution) are
    the paper's evolving C/S masks.  ``residual`` (static) records the env's
    topology mode exactly as on ``SparseGraphState``: ``True``/"solution",
    ``False``/"none", or "closed" (MIS).  Row ids are NOT stored — they are
    re-derived in-jit from ``indptr`` (:func:`csr_row_ids`), keeping state
    bytes at 5·E + ~12·N per graph.
    """
    indptr: jax.Array
    indices: jax.Array
    edge_mask: jax.Array
    candidate: jax.Array
    solution: jax.Array
    residual: bool = dataclasses.field(default=True,
                                       metadata=dict(static=True))

    @property
    def batch(self) -> int:
        return self.indptr.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.indptr.shape[1] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[1]


def csr_row_ids(indptr: jax.Array, num_edges: int) -> jax.Array:
    """(B, N+1) indptr → (B, E) int32 source-row id per edge slot, in-jit.

    ``row_ids[j] = #{i ∈ 1..N-1 : indptr[i] ≤ j}`` — an inclusive cumsum of
    +1 increments scattered at the interior row boundaries.  Consecutive
    empty rows stack their increments at one slot (``.add`` accumulates);
    boundaries at E (empty tail rows) are out of bounds and dropped
    (``mode="drop"``); padded edge slots land on the last row, where
    ``edge_mask`` zeroes their contributions.
    """
    def one(iptr):
        inc = jnp.zeros((num_edges,), jnp.int32).at[iptr[1:-1]].add(
            1, mode="drop")
        return jnp.cumsum(inc)
    return jax.vmap(one)(indptr)


def csr_segment_sum(values: jax.Array, row_ids: jax.Array,
                    num_nodes: int) -> jax.Array:
    """Per-row reduction: (B, E) edge values → (B, N) node sums.

    CSR row ids are non-decreasing by construction (``csr_row_ids`` is a
    cumsum), so the sorted-segment reduction applies —
    ``indices_are_sorted`` lets XLA skip the general scatter's conflict
    handling.  Bit-identical to the scatter-add formulation (kept below as
    :func:`csr_segment_sum_scatter` for the benchmark's before/after
    delta and the parity test)."""
    def one(vb, rb):
        return jax.ops.segment_sum(vb, rb, num_segments=num_nodes,
                                   indices_are_sorted=True)
    return jax.vmap(one)(values, row_ids)


def csr_segment_sum_scatter(values: jax.Array, row_ids: jax.Array,
                            num_nodes: int) -> jax.Array:
    """Reference scatter-add formulation of :func:`csr_segment_sum` (the
    pre-optimization path; see `benchmarks/sparse_vs_dense.py`)."""
    def one(vb, rb):
        return jnp.zeros((num_nodes,), vb.dtype).at[rb].add(vb)
    return jax.vmap(one)(values, row_ids)


def csr_segment_max(values: jax.Array, row_ids: jax.Array,
                    num_nodes: int) -> jax.Array:
    """Per-row scatter-max of NON-NEGATIVE edge values (init is zero, so
    rows with no edges — and masked-out padding — read 0)."""
    def one(vb, rb):
        return jnp.zeros((num_nodes,), vb.dtype).at[rb].max(vb)
    return jax.vmap(one)(values, row_ids)


def csr_residual_edge_mask(indices: jax.Array, edge_mask: jax.Array,
                           row_ids: jax.Array,
                           solution: jax.Array) -> jax.Array:
    """(B, E) float residual-edge factors: mask ∧ keep[row] ∧ keep[col] —
    the CSR analogue of :func:`residual_edge_mask` (and of the dense
    :func:`residual_adjacency` rewrite, derived instead of stored)."""
    keep = 1.0 - solution
    keep_pad = jnp.pad(keep, ((0, 0), (0, 1)))              # sentinel slot
    keep_col = jax.vmap(lambda kb, ib: kb[ib])(keep_pad, indices)
    keep_row = jax.vmap(lambda kb, rb: kb[rb])(keep, row_ids)
    return edge_mask.astype(jnp.float32) * keep_col * keep_row


def csr_closed_neighborhood_keep(indices: jax.Array, edge_mask: jax.Array,
                                 row_ids: jax.Array,
                                 solution: jax.Array) -> jax.Array:
    """(B, N) keep factors for CLOSED-neighborhood removal (MIS): a node
    survives iff neither in ``solution`` nor adjacent to it.  Segment-max
    of sol[col] over each row plays the role of the sparse rep's masked
    ``max(-1)``."""
    sol_pad = jnp.pad(solution, ((0, 0), (0, 1)))           # sentinel slot
    s_col = jax.vmap(lambda sb, ib: sb[ib])(sol_pad, indices)
    any_nbr = csr_segment_max(edge_mask.astype(jnp.float32) * s_col,
                              row_ids, solution.shape[1])
    return (1.0 - solution) * (1.0 - any_nbr)


def csr_batch_from_dense(adj: np.ndarray,
                         max_edges: Optional[int] = None) -> CsrGraphBatch:
    """adj (B, N, N) → flat CSR arrays with a common edge capacity
    (vectorized: one ``np.nonzero`` + cumcounts, no per-node loop).

    ``max_edges`` of None or 0 derives the capacity from the batch; an
    explicit value below the true max directed-edge count raises rather
    than silently dropping edges (same contract as
    :func:`sparse_batch_from_dense`)."""
    adj = np.asarray(adj)
    if adj.ndim == 2:
        adj = adj[None]
    b, n, _ = adj.shape
    bi, rows, cols = np.nonzero(adj > 0)        # C-order: sorted by (bi, row)
    per_graph = np.bincount(bi, minlength=b)
    true_e = int(per_graph.max(initial=0))
    if not max_edges:                           # None or 0 → derive
        me = max(true_e, 1)
    elif max_edges < true_e:
        raise ValueError(
            f"max_edges={max_edges} is below the batch's true directed edge "
            f"count {true_e}; refusing to silently drop edges")
    else:
        me = max_edges
    indices = np.full((b, me), n, np.int32)
    mask = np.zeros((b, me), bool)
    starts = np.concatenate([[0], np.cumsum(per_graph)[:-1]])
    pos = np.arange(len(bi)) - starts[bi]
    indices[bi, pos] = cols
    mask[bi, pos] = True
    rowcounts = np.bincount(bi * n + rows, minlength=b * n).reshape(b, n)
    indptr = np.zeros((b, n + 1), np.int32)
    np.cumsum(rowcounts, axis=1, out=indptr[:, 1:])
    return CsrGraphBatch(indptr=jnp.asarray(indptr),
                         indices=jnp.asarray(indices),
                         edge_mask=jnp.asarray(mask))


def csr_batch_from_arrays(indptr: np.ndarray, indices: np.ndarray,
                          max_edges: Optional[int] = None) -> CsrGraphBatch:
    """Single resident graph (indptr (N+1,), indices (E,)) → a B=1
    :class:`CsrGraphBatch`, optionally padded to ``max_edges`` slots.
    This is the zero-copy on-ramp from :func:`cached_ba_csr` output to the
    solver — no dense adjacency is ever materialized."""
    indptr = np.asarray(indptr, np.int32)
    indices = np.asarray(indices, np.int32)
    n = len(indptr) - 1
    e = len(indices)
    me = max_edges if max_edges else max(e, 1)
    if me < e:
        raise ValueError(
            f"max_edges={me} is below the graph's directed edge count {e}; "
            f"refusing to silently drop edges")
    idx = np.full((me,), n, np.int32)
    idx[:e] = indices
    mask = np.zeros((me,), bool)
    mask[:e] = True
    return CsrGraphBatch(indptr=jnp.asarray(indptr)[None],
                         indices=jnp.asarray(idx)[None],
                         edge_mask=jnp.asarray(mask)[None])


def csr_batch_to_dense(g: CsrGraphBatch) -> np.ndarray:
    """(B, N, N) dense adjacency from a CSR batch — parity-test helper."""
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    mask = np.asarray(g.edge_mask)
    b, n = indptr.shape[0], indptr.shape[1] - 1
    a = np.zeros((b, n, n), np.float32)
    for i in range(b):
        rows = np.repeat(np.arange(n), np.diff(indptr[i]))
        cols = indices[i][mask[i]]
        a[i, rows, cols] = 1.0
    return a


def csr_init_state(g: CsrGraphBatch) -> CsrGraphState:
    """Fresh CSR state: empty solution; candidates = degree > 0."""
    deg = g.indptr[:, 1:] - g.indptr[:, :-1]
    return CsrGraphState(
        indptr=g.indptr, indices=g.indices, edge_mask=g.edge_mask,
        candidate=(deg > 0).astype(jnp.float32),
        solution=jnp.zeros((g.batch, g.num_nodes), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Streaming edge-list generation + CSR assembly for paper-scale graphs
# (§6.4: N ≥ 1M, 10M+ edges).  Everything below is vectorized numpy — no
# dense (N, N) array and no Python per-node loop ever exists.
# ---------------------------------------------------------------------------

def barabasi_albert_edges(n: int, d: int = 4, *,
                          seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """BA(n, d) as a directed edge list (src, dst) — O(E) memory and time.

    Vectorized Batagelj–Brandes copy model: edge t's target is a uniform
    draw r[t] from the 2t endpoints of earlier edges.  Even draws resolve
    to a known source (``src[r/2]``); odd draws point at another edge's
    *target* and are resolved by pointer-chasing ``rr ← r[(rr-1)/2]``
    (strictly decreasing, so the chase terminates).  Uniform-over-endpoints
    IS degree-proportional sampling — the same trick as the dense
    :func:`barabasi_albert`, without its per-node loop.  Repeated draws
    within one node's d attachments collapse at dedupe time, so realized
    degree can be slightly below d (standard for this model).
    """
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
    dst[0] = 0                         # edge 0 has no predecessors: 1 → 0
    return src, dst


def csr_from_edges(n: int, src: np.ndarray, dst: np.ndarray, *,
                   symmetrize: bool = True,
                   dedupe: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Directed edge list → (indptr (N+1,) int32, indices (E,) int32) CSR,
    fully vectorized.  Self-loops are dropped; ``symmetrize`` mirrors every
    edge (undirected convention); ``dedupe`` removes repeats via a sort on
    the int64 key ``src·n + dst`` (which also yields CSR row-major order).
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if symmetrize:
        src, dst = (np.concatenate([src, dst]), np.concatenate([dst, src]))
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = src * np.int64(n) + dst
    if dedupe:
        key = np.unique(key)
        src, dst = key // n, key % n
    else:
        order = np.argsort(key, kind="stable")
        src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=n)
    indptr = np.zeros((n + 1,), np.int64)
    np.cumsum(deg, out=indptr[1:])
    return indptr.astype(np.int32), dst.astype(np.int32)


_DATA_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "data"


def cached_ba_csr(n: int, d: int = 4, *, seed: int,
                  cache_dir=None) -> Tuple[np.ndarray, np.ndarray]:
    """BA(n, d) as CSR arrays, cached as ``.npz`` under experiments/data/
    so the 10M-edge scaling bench doesn't regenerate the graph per run."""
    cache = pathlib.Path(cache_dir) if cache_dir else _DATA_DIR
    cache.mkdir(parents=True, exist_ok=True)
    path = cache / f"ba_n{n}_d{d}_s{seed}.npz"
    if path.exists():
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    src, dst = barabasi_albert_edges(n, d, seed=seed)
    indptr, indices = csr_from_edges(n, src, dst)
    np.savez_compressed(path, indptr=indptr, indices=indices)
    return indptr, indices


# ---------------------------------------------------------------------------
# Spatially partitioned view (paper §4.1): row-block of A plus local C/S.
# Used by repro.core.spatial inside shard_map; each device sees the block
# for its N/P resident nodes (dense) or its (B, N/P, D) neighbor-list rows
# (sparse — the paper's distributed sparse graph storage).
# ---------------------------------------------------------------------------

def pad_nodes(a: np.ndarray, p: int) -> np.ndarray:
    """Pad node count up to a multiple of p (isolated padding nodes — they
    have degree 0 so they are never candidates and never affect MVC)."""
    n = a.shape[-1]
    n_pad = (-n) % p
    if n_pad == 0:
        return a
    widths = [(0, 0)] * (a.ndim - 2) + [(0, n_pad), (0, n_pad)]
    return np.pad(a, widths)


# ---------------------------------------------------------------------------
# Padded edge-list ("CSR-like") sparse storage — the memory-saving
# representation for big graphs (paper §5.2 counts 20·N²ρ/P bytes for COO;
# padded edge lists cost 4·N·maxdeg/P and are TPU-gatherable).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PaddedEdgeList:
    """neighbors: (N, max_deg) int32, padded with N (a sentinel row);
    valid: (N, max_deg) bool."""
    neighbors: np.ndarray
    valid: np.ndarray

    @property
    def num_nodes(self) -> int:
        return self.neighbors.shape[0]

    def nbytes(self) -> int:
        return self.neighbors.nbytes + self.valid.nbytes


def to_padded_edgelist(a: np.ndarray, max_deg: Optional[int] = None) -> PaddedEdgeList:
    n = a.shape[-1]
    rows, cols = np.nonzero(a > 0)
    deg = np.bincount(rows, minlength=n)
    md = int(deg.max(initial=0)) if max_deg is None else max_deg
    nbr = np.full((n, md), n, dtype=np.int32)
    val = np.zeros((n, md), dtype=bool)
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    offs = np.arange(len(rows)) - starts[rows]
    keep = offs < md
    nbr[rows[keep], offs[keep]] = cols[keep]
    val[rows[keep], offs[keep]] = True
    return PaddedEdgeList(nbr, val)


def edgelist_to_dense(e: PaddedEdgeList) -> np.ndarray:
    n = e.num_nodes
    a = np.zeros((n, n), dtype=np.float32)
    rows = np.repeat(np.arange(n), e.neighbors.shape[1])
    cols = e.neighbors.reshape(-1)
    mask = e.valid.reshape(-1)
    a[rows[mask], cols[mask]] = 1.0
    return a
