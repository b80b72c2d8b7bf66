"""Pluggable graph-representation backends (DESIGN.md §1).

The paper's headline memory/scale win is distributed *sparse* graph storage
(§4.1, §5.2); its baseline is the dense adjacency path.  ``GraphRep``
abstracts "which representation" so the environment registry, the inference
driver (Alg. 4 with adaptive multi-node selection), the training loop
(compressed-replay re-materialization, Alg. 5 line 21) and the spatial
shard_map path all dispatch through one interface instead of forking code
paths:

- ``DenseRep``  — (B, N, N) residual adjacency, rewritten per commit.
- ``SparseRep`` — (B, N, D) padded neighbor lists + masks; topology is
  immutable, residual edges derived from the solution mask.
- ``CsrRep``    — flat (indptr, indices, edge_mask) CSR arrays; the first
  EDGE-proportional backend (no N² block, no per-node max-degree padding)
  — the rep that reaches the paper's 10M+-edge graphs (DESIGN.md §13).

Backends are singletons (``get_rep("dense"|"sparse"|"csr")``) so they can
be passed to ``jax.jit`` as static arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from .graphs import (CsrGraphBatch, CsrGraphState, GraphState,
                     SparseGraphBatch, SparseGraphState,
                     closed_neighborhood_keep, closed_neighborhood_keep_dense,
                     csr_batch_from_dense, csr_closed_neighborhood_keep,
                     csr_init_state, csr_residual_edge_mask, csr_row_ids,
                     csr_segment_sum, init_state, residual_adjacency,
                     residual_edge_mask, sparse_batch_from_dense,
                     sparse_init_state)
from .policy import PolicyParams, policy_scores
from .s2v_csr import csr_policy_scores, csr_state_bytes
from .s2v_sparse import sparse_policy_scores


class GraphRep:
    """Backend interface.  All array-returning methods are jit-traceable;
    ``prepare_dataset``/``init_state`` run host-side (numpy in, device out).
    """

    name: str = "?"

    # -- state construction -------------------------------------------------
    def init_state(self, adj):
        """(B, N, N) or (N, N) dense adjacency → fresh state."""
        raise NotImplementedError

    def prepare_dataset(self, adj_stack):
        """(G, N, N) dense training set → device-resident dataset source."""
        raise NotImplementedError

    def state_from_tuples(self, source, graph_idx, solutions,
                          residual=True, candidate_fn=None):
        """Tuples2Graphs (paper Alg. 5 line 21): re-materialize per-tuple
        states from (dataset source, graph ids, partial-solution masks).

        ``residual`` is the env's topology mode (``env.register``):
        ``"solution"``/True removes S's rows and columns (MVC),
        ``"none"``/False keeps the original topology (MaxCut, MDS), and
        ``"closed"`` removes S and its neighbors (MIS).  ``candidate_fn``
        overrides the default candidate derivation (positive residual
        degree ∧ not in S) with the env's registered rule — it receives
        the re-materialized state and returns the (B, N) mask."""
        raise NotImplementedError

    # -- policy evaluation --------------------------------------------------
    def scores(self, params: PolicyParams, state, *, num_layers: int,
               masked: bool = True, kernel: str = "fused",
               compute: str = "f32") -> jax.Array:
        """(B, N) candidate scores: Q(EM(state), C).  ``kernel``/``compute``
        select the S2V layer lowering and operand precision (DESIGN.md §12).
        """
        raise NotImplementedError

    # -- state transition ---------------------------------------------------
    def commit(self, state, sel: jax.Array):
        """Commit a (B, N) selection mask to the partial solution (Alg. 4
        lines 7-9, covering semantics — env-specific commit rules live in
        the env registry).  Returns (new_state, done)."""
        raise NotImplementedError

    # -- accounting ---------------------------------------------------------
    def state_bytes(self, state) -> int:
        """Peak per-step state footprint of this representation."""
        raise NotImplementedError

    def __repr__(self):
        return f"GraphRep({self.name})"


class DenseRep(GraphRep):
    """(B, N, N) residual adjacency — the MXU-friendly baseline."""

    name = "dense"

    def init_state(self, adj) -> GraphState:
        if isinstance(adj, GraphState):
            return adj
        return init_state(jnp.asarray(adj, jnp.float32))

    def prepare_dataset(self, adj_stack) -> jax.Array:
        return jnp.asarray(adj_stack, jnp.float32)

    def state_from_tuples(self, source, graph_idx, solutions,
                          residual=True, candidate_fn=None) -> GraphState:
        from .env import normalize_residual_mode
        mode = normalize_residual_mode(residual)
        sol = jnp.asarray(solutions, jnp.float32)
        base = source[jnp.asarray(graph_idx)]
        if mode == "solution":
            adj = residual_adjacency(base, sol)
            cand = ((adj.sum(-1) > 0) & (sol < 0.5)).astype(jnp.float32)
        elif mode == "none":
            adj = base
            cand = ((adj.sum(-1) > 0) & (sol < 0.5)).astype(jnp.float32)
        else:                                # closed: drop S and N(S)
            keep = closed_neighborhood_keep_dense(base, sol)
            adj = base * keep[:, :, None] * keep[:, None, :]
            cand = ((base.sum(-1) > 0) & (keep > 0.5)).astype(jnp.float32)
        state = GraphState(adj=adj, candidate=cand, solution=sol)
        if candidate_fn is not None:
            state = dataclasses.replace(state,
                                        candidate=candidate_fn(state))
        return state

    def scores(self, params, state: GraphState, *, num_layers,
               masked=True, kernel="fused", compute="f32") -> jax.Array:
        """(B, N) scores.  On a row-split state (``state.row_axis``) each
        device scores its resident rows, with one collective per layer
        over the row axis (Alg. 2), and the scores are gathered whole so
        every device takes the same top-d (Alg. 4 line 6)."""
        local = policy_scores(params, state.adj,
                              state.local_rows(state.solution),
                              state.local_rows(state.candidate),
                              num_layers=num_layers, axis=state.row_axis,
                              masked=masked, kernel=kernel, compute=compute)
        if state.row_axis is None:
            return local
        with jax.named_scope("q.head"):
            return state.all_rows(local)

    def commit(self, state: GraphState, sel):
        """Remove the selected nodes' rows and columns; on a row-split
        state each device rewrites only its resident rows."""
        solution = jnp.maximum(state.solution, sel)
        keep = 1.0 - sel
        new = dataclasses.replace(state, adj=state.masked(keep),
                                  solution=solution)
        deg = new.degree()
        candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
        done = new.total() == 0
        return dataclasses.replace(new, candidate=candidate), done

    def state_bytes(self, state: GraphState) -> int:
        return int(state.adj.size * state.adj.dtype.itemsize
                   + state.candidate.size * 4 + state.solution.size * 4)


class SparseRep(GraphRep):
    """(B, N, D) padded neighbor lists — O(N·maxdeg) state, immutable
    topology, residual edges derived from the solution mask (paper §5.2)."""

    name = "sparse"

    def __init__(self, max_degree: Optional[int] = None):
        self.max_degree = max_degree

    def init_state(self, adj) -> SparseGraphState:
        if isinstance(adj, SparseGraphState):
            return adj
        if isinstance(adj, SparseGraphBatch):
            return sparse_init_state(adj)
        g = sparse_batch_from_dense(np.asarray(adj), self.max_degree)
        return sparse_init_state(g)

    def prepare_dataset(self, adj_stack) -> SparseGraphBatch:
        return sparse_batch_from_dense(np.asarray(adj_stack), self.max_degree)

    def state_from_tuples(self, source: SparseGraphBatch, graph_idx,
                          solutions, residual=True, candidate_fn=None
                          ) -> SparseGraphState:
        from .env import normalize_residual_mode
        mode = normalize_residual_mode(residual)
        sol = jnp.asarray(solutions, jnp.float32)
        gi = jnp.asarray(graph_idx)
        nbrs, valid = source.neighbors[gi], source.valid[gi]
        if mode == "solution":
            deg = residual_edge_mask(nbrs, valid, sol).sum(-1)
            cand = ((deg > 0) & (sol < 0.5)).astype(jnp.float32)
            flag = True
        elif mode == "none":
            deg = valid.sum(-1)
            cand = ((deg > 0) & (sol < 0.5)).astype(jnp.float32)
            flag = False
        else:                                # closed: drop S and N(S)
            keep = closed_neighborhood_keep(nbrs, valid, sol)
            cand = ((valid.sum(-1) > 0) & (keep > 0.5)).astype(jnp.float32)
            flag = mode
        state = SparseGraphState(neighbors=nbrs, valid=valid,
                                 candidate=cand, solution=sol,
                                 residual=flag)
        if candidate_fn is not None:
            state = dataclasses.replace(state,
                                        candidate=candidate_fn(state))
        return state

    def scores(self, params, state: SparseGraphState, *, num_layers,
               masked=True, kernel="fused", compute="f32") -> jax.Array:
        return sparse_policy_scores(params, state, state.solution,
                                    state.candidate, num_layers=num_layers,
                                    masked=masked, residual=state.residual,
                                    kernel=kernel, compute=compute)

    def commit(self, state: SparseGraphState, sel):
        solution = jnp.maximum(state.solution, sel)
        edge = residual_edge_mask(state.neighbors, state.valid, solution)
        deg = edge.sum(-1)
        candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
        done = edge.sum((-1, -2)) == 0
        return SparseGraphState(neighbors=state.neighbors, valid=state.valid,
                                candidate=candidate, solution=solution,
                                residual=state.residual), done

    def state_bytes(self, state: SparseGraphState) -> int:
        return int(state.neighbors.size * 4 + state.valid.size
                   + state.candidate.size * 4 + state.solution.size * 4)


class CsrRep(GraphRep):
    """Flat (indptr, indices, edge_mask) CSR arrays — O(E) state, immutable
    topology, residual edges derived from the solution mask (DESIGN.md
    §13).  ``max_edges`` pins the padded edge capacity (serving buckets);
    None derives it per batch."""

    name = "csr"

    def __init__(self, max_edges: Optional[int] = None):
        self.max_edges = max_edges

    def init_state(self, adj) -> CsrGraphState:
        if isinstance(adj, CsrGraphState):
            return adj
        if isinstance(adj, CsrGraphBatch):
            return csr_init_state(adj)
        g = csr_batch_from_dense(np.asarray(adj), self.max_edges)
        return csr_init_state(g)

    def prepare_dataset(self, adj_stack) -> CsrGraphBatch:
        return csr_batch_from_dense(np.asarray(adj_stack), self.max_edges)

    def state_from_tuples(self, source: CsrGraphBatch, graph_idx,
                          solutions, residual=True, candidate_fn=None
                          ) -> CsrGraphState:
        from .env import normalize_residual_mode
        mode = normalize_residual_mode(residual)
        sol = jnp.asarray(solutions, jnp.float32)
        gi = jnp.asarray(graph_idx)
        indptr = source.indptr[gi]
        indices = source.indices[gi]
        mask = source.edge_mask[gi]
        rid = csr_row_ids(indptr, indices.shape[1])
        if mode == "solution":
            deg = _csr_degree(indices, mask, rid, sol, "solution",
                              sol.shape[1])
            cand = ((deg > 0) & (sol < 0.5)).astype(jnp.float32)
            flag = True
        elif mode == "none":
            deg = _csr_degree(indices, mask, rid, sol, "none", sol.shape[1])
            cand = ((deg > 0) & (sol < 0.5)).astype(jnp.float32)
            flag = False
        else:                                # closed: drop S and N(S)
            keep = csr_closed_neighborhood_keep(indices, mask, rid, sol)
            deg0 = _csr_degree(indices, mask, rid, sol, "none", sol.shape[1])
            cand = ((deg0 > 0) & (keep > 0.5)).astype(jnp.float32)
            flag = mode
        state = CsrGraphState(indptr=indptr, indices=indices, edge_mask=mask,
                              candidate=cand, solution=sol, residual=flag)
        if candidate_fn is not None:
            state = dataclasses.replace(state,
                                        candidate=candidate_fn(state))
        return state

    def scores(self, params, state: CsrGraphState, *, num_layers,
               masked=True, kernel="fused", compute="f32") -> jax.Array:
        return csr_policy_scores(params, state, state.solution,
                                 state.candidate, num_layers=num_layers,
                                 masked=masked, residual=state.residual,
                                 kernel=kernel, compute=compute)

    def commit(self, state: CsrGraphState, sel):
        solution = jnp.maximum(state.solution, sel)
        rid = csr_row_ids(state.indptr, state.indices.shape[1])
        edge = csr_residual_edge_mask(state.indices, state.edge_mask, rid,
                                      solution)
        deg = csr_segment_sum(edge, rid, state.num_nodes)
        candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
        done = edge.sum(-1) == 0
        return dataclasses.replace(state, candidate=candidate,
                                   solution=solution), done

    def state_bytes(self, state: CsrGraphState) -> int:
        return int(csr_state_bytes(state))


def _csr_degree(indices, mask, rid, sol, mode, n):
    """(B, N) per-node degree under the given residual mode."""
    if mode == "solution":
        edge = csr_residual_edge_mask(indices, mask, rid, sol)
    else:
        edge = mask.astype(jnp.float32)
    return csr_segment_sum(edge, rid, n)


DENSE = DenseRep()
SPARSE = SparseRep()
CSR = CsrRep()

_REPS: Dict[str, GraphRep] = {"dense": DENSE, "sparse": SPARSE, "csr": CSR}


def get_rep(rep: Union[str, GraphRep, None]) -> GraphRep:
    """Resolve a representation name/instance to a backend singleton."""
    if rep is None:
        return DENSE
    if isinstance(rep, GraphRep):
        return rep
    try:
        return _REPS[rep]
    except KeyError:
        raise ValueError(f"unknown graph representation {rep!r}; "
                         f"available: {sorted(_REPS)}") from None


def rep_names():
    return sorted(_REPS)


def rep_for_state(state) -> GraphRep:
    """Dispatch on a state's type (environment/agent polymorphism)."""
    if isinstance(state, CsrGraphState):
        return CSR
    return SPARSE if isinstance(state, SparseGraphState) else DENSE
