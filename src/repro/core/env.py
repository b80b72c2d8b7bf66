"""Graph learning environments (paper §3, Fig 1).

Functional, fully on-device environments: ``step(state, action) -> (state,
reward, done)``.  The paper runs the env on host CPUs next to each GPU; on TPU
we keep it on-device (the update is a masked row/column zeroing — pure VPU
work) to avoid host round-trips per RL step.  This is a documented hardware
adaptation (DESIGN.md §2).

Environments are registered by name so users can plug in new graph problems
(the paper's extensibility claim), and every registered step is
representation-polymorphic: it accepts either a dense ``GraphState`` or a
``SparseGraphState`` (DESIGN.md §1) and returns a state of the same
representation.  On the sparse path the topology is never rewritten — only
the C/S masks update.

The problem suite is MVC, MaxCut, MIS (maximum independent set) and MDS
(minimum dominating set).  Each registration declares (DESIGN.md §11):

- ``residual`` — what topology the policy sees: ``"solution"`` (MVC:
  committing a node deletes its edges), ``"none"`` (MaxCut/MDS: topology
  untouched), or ``"closed"`` (MIS: committing a node removes it AND its
  neighbors).  Replay re-materialization and the sparse scorer's edge
  factors follow this mode.
- ``commit`` — the Alg. 4 top-d commit/termination rule.
- ``candidates`` — how the candidate set derives from (topology, S) when
  the default "positive residual degree, not in S" rule is wrong (MDS:
  a candidate must still cover an uncovered node).
- ``prune`` — an optional constraint filter on the top-d selection mask
  (MIS: a raw top-d set can contain adjacent nodes; committing them
  together would break independence).
- ``checker`` — the batched feasibility predicate on (original adjacency,
  solution mask) used by tests/benchmarks.
- ``sense`` — ``"min"`` or ``"max"``, for quality ratios vs heuristics.

**Padding-safety contract** (enforced, not assumed): the serving layer
pads graphs with degree-0 isolated nodes and empty batch rows
(``repro.serving.bucketing``), so an environment is only servable if its
candidate derivation can NEVER admit a degree-0 node — at init or any
later partial solution.  ``ensure_padding_safe`` probes each env's real
candidate path against an isolated-node graph; ``init_solve_state`` and
``plan_batches`` call it and fail fast with an actionable error for
unsafe registrations.  For MDS this forces the documented convention:
isolated nodes count as already dominated (they are padding, not
problem nodes); ``is_dominating_set`` checks exactly that.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .graphs import (CsrGraphState, GraphState, SparseGraphState,
                     closed_neighborhood_keep,
                     csr_closed_neighborhood_keep, csr_residual_edge_mask,
                     csr_row_ids, csr_segment_max, csr_segment_sum,
                     init_state, residual_edge_mask)
from .qmodel import NEG_INF

EnvStep = Callable[[GraphState, jax.Array], Tuple[GraphState, jax.Array, jax.Array]]
# (state, sel mask) -> (state, done): the inference driver's commit rule
CommitFn = Callable[[GraphState, jax.Array], Tuple[GraphState, jax.Array]]
# state (topology + solution authoritative) -> (B, N) candidate mask
CandidateFn = Callable[[GraphState], jax.Array]
# (state, sel, scores) -> sel: constraint filter on the top-d selection
PruneFn = Callable[[GraphState, jax.Array, jax.Array], jax.Array]

RESIDUAL_MODES = ("solution", "none", "closed")
_MAX_COMMIT = 8               # == inference.MAX_D (top-d selection width)

_REGISTRY: Dict[str, EnvStep] = {}
_MODE: Dict[str, str] = {}
_COMMIT: Dict[str, CommitFn] = {}
_CANDIDATES: Dict[str, Optional[CandidateFn]] = {}
_PRUNE: Dict[str, Optional[PruneFn]] = {}
_CHECKER: Dict[str, Callable] = {}
_SENSE: Dict[str, str] = {}
_PADDING_SAFE: Dict[str, bool] = {}


def normalize_residual_mode(residual: Union[bool, str]) -> str:
    """``register``'s ``residual`` argument → canonical mode string.
    Back-compat: ``True`` is ``"solution"``, ``False`` is ``"none"``."""
    if residual is True:
        return "solution"
    if residual is False:
        return "none"
    if residual in RESIDUAL_MODES:
        return residual
    raise ValueError(f"unknown residual mode {residual!r}; expected a bool "
                     f"or one of {RESIDUAL_MODES}")


def always_feasible(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """Default checker: every 0/1 assignment is feasible (MaxCut)."""
    return jnp.ones(solution.shape[:-1], bool)


def residual_commit(state, sel: jax.Array):
    """Covering-problem commit (Alg. 4 lines 7-9, "solution" mode):
    committing a node removes its incident edges from the residual graph;
    done when no edge survives.  Delegates to the state's GraphRep backend
    (dense rewrites ``adj``, sparse only updates masks)."""
    from .graphrep import rep_for_state
    return rep_for_state(state).commit(state, sel)


def assignment_commit(state, sel: jax.Array):
    """Assignment-problem commit (MaxCut family): committing a node assigns
    it to S without touching the topology; done when no candidate remains.
    Works on both representations — only the C/S masks update."""
    solution = jnp.maximum(state.solution, sel)
    candidate = jnp.clip(state.candidate - sel, 0.0, 1.0)
    done = candidate.sum(-1) == 0
    # only the C/S masks change — identical across all three representations
    new = dataclasses.replace(state, candidate=candidate, solution=solution)
    return new, done


def register(name: str, residual: Union[bool, str] = True,
             commit: Optional[CommitFn] = None,
             candidates: Optional[CandidateFn] = None,
             prune: Optional[PruneFn] = None,
             checker: Optional[Callable] = None,
             sense: str = "min"):
    """Register an environment step (the DESIGN.md §11 extension point).

    ``residual`` declares what topology the policy sees — ``"solution"``
    (True: MVC semantics, committing a node deletes its edges), ``"none"``
    (False: MaxCut/MDS, the original topology), or ``"closed"`` (MIS,
    committing a node removes it and its neighbors); the GraphRep backends
    re-materialize replay states accordingly.

    ``commit`` is the problem's top-d commit/termination rule for the
    Alg. 4 inference driver (``repro.core.inference.solve``); it defaults
    to :func:`residual_commit` (covering semantics) for residual modes and
    :func:`assignment_commit` otherwise, and must be jit-traceable on both
    representations.  ``candidates`` overrides the default candidate
    derivation (positive residual degree ∧ not in S) wherever states are
    (re)built; ``prune`` filters the raw top-d selection mask before the
    commit (MIS independence); ``checker`` is the batched feasibility
    predicate ``(original dense adjacency, solution) -> (B,) bool``;
    ``sense`` records whether solution size/value is minimized or
    maximized."""
    mode = normalize_residual_mode(residual)
    if sense not in ("min", "max"):
        raise ValueError(f"sense must be 'min' or 'max', got {sense!r}")

    def deco(fn):
        _REGISTRY[name] = fn
        _MODE[name] = mode
        _COMMIT[name] = commit or (assignment_commit if mode == "none"
                                   else residual_commit)
        _CANDIDATES[name] = candidates
        _PRUNE[name] = prune
        _CHECKER[name] = checker or always_feasible
        _SENSE[name] = sense
        _PADDING_SAFE.pop(name, None)       # re-probe on re-registration
        return fn
    return deco


def unregister(name: str) -> None:
    """Remove an environment (test scaffolding for throwaway envs)."""
    for table in (_REGISTRY, _MODE, _COMMIT, _CANDIDATES, _PRUNE,
                  _CHECKER, _SENSE, _PADDING_SAFE):
        table.pop(name, None)


def _lookup(table: Dict, name: str):
    try:
        return table[name]
    except KeyError:
        raise ValueError(f"unknown environment {name!r}; registered: "
                         f"{names()}") from None


def make(name: str) -> EnvStep:
    return _lookup(_REGISTRY, name)


def residual_mode(name: str) -> str:
    """The env's topology mode: "solution" | "none" | "closed"."""
    return _lookup(_MODE, name)


def residual_semantics(name: str) -> bool:
    """Back-compat boolean view of :func:`residual_mode` (True for any
    residual-rewriting mode)."""
    return residual_mode(name) != "none"


def sparse_residual_flag(name: str) -> Union[bool, str]:
    """The value a ``SparseGraphState.residual`` static field carries for
    this env: True ("solution"), False ("none"), or the mode string."""
    mode = residual_mode(name)
    return {"solution": True, "none": False}.get(mode, mode)


def commit_rule(name: str) -> CommitFn:
    """The problem's commit/termination rule (solve's stop condition is
    env-polymorphic: MVC stops on an empty residual edge set, MaxCut on an
    empty candidate set)."""
    return _lookup(_COMMIT, name)


def candidate_rule(name: str) -> Optional[CandidateFn]:
    """The env's candidate derivation override (None → the GraphRep
    default: positive residual degree ∧ not in S)."""
    return _lookup(_CANDIDATES, name)


def prune_rule(name: str) -> Optional[PruneFn]:
    """Optional constraint filter applied to the top-d selection mask
    before the commit (None for unconstrained multi-commits)."""
    return _lookup(_PRUNE, name)


def checker(name: str) -> Callable:
    """Batched feasibility predicate ``(adj0, solution) -> (B,) bool``."""
    return _lookup(_CHECKER, name)


def sense(name: str) -> str:
    """"min" | "max" — the optimization direction of |S| / the objective."""
    return _lookup(_SENSE, name)


def names():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Padding-safety contract (DESIGN.md §9/§11): the serving layer's bucketing
# pads with isolated nodes and empty rows, which is only sound if degree-0
# nodes can never enter the candidate set.  This was an unchecked docstring
# assumption in repro.serving.bucketing; it is now probed per env against
# the REAL candidate-derivation path and enforced at init_solve_state /
# plan_batches time.
# ---------------------------------------------------------------------------

def _probe_padding_safety(name: str) -> bool:
    """Drive the env's actual candidate derivation (state_from_tuples with
    the registered mode + candidate rule, plus one env step) on a probe
    graph containing isolated padding-style nodes, and report whether any
    degree-0 node ever becomes a candidate.  Candidate rules and env
    steps are representation-polymorphic with separate code per backend,
    so ALL THREE backend paths are probed (the service builds SparseRep /
    CsrRep states when ``cfg.graph_rep`` selects them)."""
    from .graphrep import CSR, DENSE, SPARSE
    # probe: nodes 0-1 share the only edge; nodes 2 and 3 are isolated —
    # exactly the shape pad_adjacency produces.
    adj = np.zeros((1, 4, 4), np.float32)
    adj[0, 0, 1] = adj[0, 1, 0] = 1.0
    mode, cand_fn = _MODE[name], _CANDIDATES[name]
    gi = np.zeros((1,), np.int32)
    for rep in (DENSE, SPARSE, CSR):
        source = rep.prepare_dataset(adj)
        for sol in ([0, 0, 0, 0], [1, 0, 0, 0], [1, 1, 0, 0]):
            st = rep.state_from_tuples(
                source, gi, np.asarray([sol], np.float32),
                residual=mode, candidate_fn=cand_fn)
            if np.asarray(st.candidate)[0, 2:].any():
                return False
        # one real transition from the fresh state must keep padding out
        st = rep.state_from_tuples(source, gi,
                                   np.zeros((1, 4), np.float32),
                                   residual=mode, candidate_fn=cand_fn)
        st, _, _ = _REGISTRY[name](st, jnp.asarray([0]))
        if np.asarray(st.candidate)[0, 2:].any():
            return False
    return True


def ensure_padding_safe(name: str) -> None:
    """Raise unless ``name``'s candidate derivation provably excludes
    degree-0 nodes (the serving layer's padding).  Probed once per env and
    cached; called by ``init_solve_state`` and ``plan_batches``."""
    _lookup(_REGISTRY, name)
    safe = _PADDING_SAFE.get(name)
    if safe is None:
        safe = _probe_padding_safety(name)
        _PADDING_SAFE[name] = safe
    if not safe:
        raise ValueError(
            f"environment {name!r} violates the padding-safety contract: "
            f"its candidate derivation admits degree-0 (isolated) nodes. "
            f"The solver service pads every graph with isolated nodes and "
            f"empty batch rows (repro.serving.bucketing), so such an env "
            f"would score/commit padding. Derive candidates so deg==0 "
            f"nodes are excluded — e.g. treat isolated nodes as already "
            f"satisfied, as the 'mds' env does — or register a custom "
            f"`candidates` rule that masks them (DESIGN.md §11).")


def _onehot(v: jax.Array, n: int) -> jax.Array:
    return jax.nn.one_hot(v, n, dtype=jnp.float32)


def _mvc_step_dense(state: GraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)                                 # (B, N)
    solution = jnp.maximum(state.solution, oh)
    keep = 1.0 - oh
    adj = state.adj * keep[:, :, None] * keep[:, None, :]
    # candidates: not in solution and still incident to an uncovered edge
    deg = adj.sum(-1)
    candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
    reward = -jnp.ones((b,), jnp.float32)
    done = adj.sum((-1, -2)) == 0
    return GraphState(adj=adj, candidate=candidate, solution=solution), reward, done


def _mvc_step_sparse(state: SparseGraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)
    solution = jnp.maximum(state.solution, oh)
    # residual edges derive from the immutable topology + updated S
    edge = residual_edge_mask(state.neighbors, state.valid, solution)
    deg = edge.sum(-1)
    candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
    reward = -jnp.ones((b,), jnp.float32)
    done = edge.sum((-1, -2)) == 0
    return SparseGraphState(neighbors=state.neighbors, valid=state.valid,
                            candidate=candidate, solution=solution), reward, done


def _mvc_step_csr(state: CsrGraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)
    solution = jnp.maximum(state.solution, oh)
    rid = csr_row_ids(state.indptr, state.indices.shape[1])
    edge = csr_residual_edge_mask(state.indices, state.edge_mask, rid,
                                  solution)
    deg = csr_segment_sum(edge, rid, n)
    candidate = ((deg > 0) & (solution < 0.5)).astype(jnp.float32)
    reward = -jnp.ones((b,), jnp.float32)
    done = edge.sum(-1) == 0
    return dataclasses.replace(state, candidate=candidate,
                               solution=solution), reward, done


@register("mvc", checker=lambda adj0, sol: is_cover(adj0, sol))
def mvc_step(state, action: jax.Array):
    """Minimum Vertex Cover step (paper §4, Fig 3/4).

    action: (B,) int32 node ids.  Adds the node to the partial solution,
    removes it from candidates, and removes its incident edges from the
    residual graph (dense: zeroes its row+column; sparse/csr: the residual
    edge mask drops them).  Reward is -1 per selected node (minimize |S|);
    done when no edges remain.
    """
    if isinstance(state, CsrGraphState):
        return _mvc_step_csr(state, action)
    if isinstance(state, SparseGraphState):
        return _mvc_step_sparse(state, action)
    return _mvc_step_dense(state, action)


def _maxcut_step_dense(state: GraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)
    in_s = state.solution
    # gain = deg_to_other_side - deg_to_same_side for the chosen node
    nbrs = jnp.einsum("bn,bnm->bm", oh, state.adj)          # (B, N) neighbors of v
    to_s = (nbrs * in_s).sum(-1)
    to_out = (nbrs * (1.0 - in_s)).sum(-1)
    reward = to_out - to_s
    solution = jnp.maximum(in_s, oh)
    candidate = jnp.clip(state.candidate - oh, 0.0, 1.0)
    done = candidate.sum(-1) == 0
    return GraphState(adj=state.adj, candidate=candidate, solution=solution), reward, done


def _maxcut_step_sparse(state: SparseGraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)
    in_s = state.solution
    # neighbor row of the chosen node: (B, D) global ids + validity
    act = action.astype(jnp.int32)[:, None, None]
    nbr_v = jnp.take_along_axis(state.neighbors, act, axis=1)[:, 0]
    val_v = jnp.take_along_axis(state.valid, act, axis=1)[:, 0].astype(jnp.float32)
    in_s_pad = jnp.pad(in_s, ((0, 0), (0, 1)))              # sentinel slot
    s_nbr = jax.vmap(lambda sb, nb: sb[nb])(in_s_pad, nbr_v)
    to_s = (val_v * s_nbr).sum(-1)
    to_out = (val_v * (1.0 - s_nbr)).sum(-1)
    reward = to_out - to_s
    solution = jnp.maximum(in_s, oh)
    candidate = jnp.clip(state.candidate - oh, 0.0, 1.0)
    done = candidate.sum(-1) == 0
    # MaxCut keeps the original topology visible to the policy (the dense
    # env keeps ``adj`` intact) — mark the state non-residual.
    return SparseGraphState(neighbors=state.neighbors, valid=state.valid,
                            candidate=candidate, solution=solution,
                            residual=False), reward, done


def _maxcut_step_csr(state: CsrGraphState, action: jax.Array):
    b, n = state.candidate.shape
    oh = _onehot(action, n)
    in_s = state.solution
    # CSR rows are ragged, so the action's incident edges are found with a
    # fixed-shape row-match mask over all E edge slots instead of a
    # per-node neighbor-row gather.
    rid = csr_row_ids(state.indptr, state.indices.shape[1])
    rm = ((rid == action.astype(jnp.int32)[:, None]) & state.edge_mask
          ).astype(jnp.float32)                              # (B, E)
    in_s_pad = jnp.pad(in_s, ((0, 0), (0, 1)))               # sentinel slot
    s_col = jax.vmap(lambda sb, ib: sb[ib])(in_s_pad, state.indices)
    to_s = (rm * s_col).sum(-1)
    to_out = (rm * (1.0 - s_col)).sum(-1)
    reward = to_out - to_s
    solution = jnp.maximum(in_s, oh)
    candidate = jnp.clip(state.candidate - oh, 0.0, 1.0)
    done = candidate.sum(-1) == 0
    # MaxCut keeps the original topology visible to the policy — mark the
    # state non-residual (same convention as the sparse step).
    return dataclasses.replace(state, candidate=candidate, solution=solution,
                               residual=False), reward, done


@register("maxcut", residual=False, sense="max")
def maxcut_step(state, action: jax.Array):
    """Maximum Cut step (second environment, demonstrating extensibility —
    the paper cites MaxCut as the canonical sibling problem [24]).

    Moving node v into set S gains (edges to V\\S) - (edges already cut to S).
    The topology stays the original adjacency (cut does not delete edges);
    candidates are all nodes not yet in S.  done when no move has positive
    gain — approximated here as "all nodes assigned" for fixed-horizon RL;
    the agent's reward signal handles quality.
    """
    if isinstance(state, CsrGraphState):
        return _maxcut_step_csr(state, action)
    if isinstance(state, SparseGraphState):
        return _maxcut_step_sparse(state, action)
    return _maxcut_step_dense(state, action)


# ---------------------------------------------------------------------------
# MIS — Maximum Independent Set (Dai et al. 2017's third S2V-DQN problem).
# Residual mode "closed": committing v removes v AND its neighbors (none of
# them can ever join S), so the policy sees the graph induced on the still-
# eligible nodes.  Candidates are the surviving ORIGINALLY-positive-degree
# nodes — including ones isolated by earlier removals (they are free +1
# picks), but never originally-isolated padding nodes.
# ---------------------------------------------------------------------------

def mis_commit(state, sel: jax.Array):
    """Closed-neighborhood commit (MIS): S gains ``sel``; ``sel`` and its
    neighbors leave the candidate pool (and, densely, the topology); done
    when no eligible node remains."""
    solution = jnp.maximum(state.solution, sel)
    if isinstance(state, CsrGraphState):
        rid = csr_row_ids(state.indptr, state.indices.shape[1])
        keep = csr_closed_neighborhood_keep(state.indices, state.edge_mask,
                                            rid, sel)
        candidate = state.candidate * keep
        done = candidate.sum(-1) == 0
        return dataclasses.replace(state, candidate=candidate,
                                   solution=solution), done
    if isinstance(state, SparseGraphState):
        keep = closed_neighborhood_keep(state.neighbors, state.valid, sel)
        candidate = state.candidate * keep
        done = candidate.sum(-1) == 0
        return SparseGraphState(neighbors=state.neighbors, valid=state.valid,
                                candidate=candidate, solution=solution,
                                residual=state.residual), done
    keep = state.closed_keep(sel)
    adj = state.masked(keep)
    candidate = state.candidate * keep
    done = candidate.sum(-1) == 0
    return dataclasses.replace(state, adj=adj, candidate=candidate,
                               solution=solution), done


def mis_prune(state, sel: jax.Array, scores: jax.Array) -> jax.Array:
    """Filter a raw top-d selection down to an independent subset.

    A top-d mask can contain adjacent candidates; committing them together
    would break independence.  Greedily keep selected nodes in descending
    score order (argmax ties break at the lowest index — deterministic, so
    the host and fused engines stay bit-identical), dropping any selected
    node adjacent to an already-kept one.
    """
    b, n = sel.shape
    if isinstance(state, CsrGraphState):
        rid = csr_row_ids(state.indptr, state.indices.shape[1])

        def keep_fn(pick):
            return csr_closed_neighborhood_keep(state.indices,
                                                state.edge_mask, rid, pick)
    elif isinstance(state, SparseGraphState):
        def keep_fn(pick):
            return closed_neighborhood_keep(state.neighbors, state.valid,
                                            pick)
    else:
        keep_fn = state.closed_keep

    def body(carry, _):
        kept, active = carry
        masked = jnp.where(active > 0.5, scores, NEG_INF)
        idx = jnp.argmax(masked, axis=-1)
        has = (active.sum(-1) > 0).astype(jnp.float32)
        pick = _onehot(idx, n) * has[:, None]
        keep = keep_fn(pick)
        return (jnp.maximum(kept, pick), active * keep), None

    (kept, _), _ = lax.scan(body, (jnp.zeros_like(sel), sel), None,
                            length=_MAX_COMMIT)
    return kept


@register("mis", residual="closed", commit=mis_commit, prune=mis_prune,
          checker=lambda adj0, sol: is_independent_set(adj0, sol),
          sense="max")
def mis_step(state, action: jax.Array):
    """Maximum Independent Set step: adding node v to S earns +1 and
    removes v plus all its neighbors from play (closed-neighborhood
    removal); done when no eligible node remains.  Isolated PADDING nodes
    are never eligible, but nodes isolated by earlier removals stay
    eligible (each is a free +1).

    Non-candidate actions commit nothing and earn 0: unlike MVC, a
    spurious commit (the argmax-over-NEG_INF node 0 of an already-done
    row in a mixed-length training batch) would BREAK independence and
    feed fake +1 rewards into replay, so the selection is masked."""
    b, n = state.candidate.shape
    sel = _onehot(action, n) * state.candidate
    new_state, done = mis_commit(state, sel)
    reward = sel.sum(-1)
    return new_state, reward, done


# ---------------------------------------------------------------------------
# MDS — Minimum Dominating Set (the GRL survey's canonical next target).
# Residual mode "none": the topology never changes; the closed-neighborhood
# cover state derives from (topology, S).  Padding convention: isolated
# nodes count as already dominated (they are padding, not problem nodes) —
# this is exactly what makes MDS servable through padded buckets.
# ---------------------------------------------------------------------------

def _covered_and_need(state):
    """(covered, need): closed-neighborhood coverage of S and the mask of
    nodes that require domination (positive original degree)."""
    sol = state.solution
    if isinstance(state, CsrGraphState):
        rid = csr_row_ids(state.indptr, state.indices.shape[1])
        em = state.edge_mask.astype(jnp.float32)
        deg0 = csr_segment_sum(em, rid, sol.shape[1])
        sol_pad = jnp.pad(sol, ((0, 0), (0, 1)))            # sentinel slot
        s_col = jax.vmap(lambda sb, ib: sb[ib])(sol_pad, state.indices)
        cov_nbr = csr_segment_max(em * s_col, rid, sol.shape[1])
    elif isinstance(state, SparseGraphState):
        val = state.valid.astype(jnp.float32)
        deg0 = val.sum(-1)
        sol_pad = jnp.pad(sol, ((0, 0), (0, 1)))            # sentinel slot
        s_nbr = jax.vmap(lambda sb, nb: sb[nb])(sol_pad, state.neighbors)
        cov_nbr = (val * s_nbr).max(-1)
    else:
        deg0 = state.degree()
        cov_nbr = (state.matvec(sol) > 0).astype(jnp.float32)
    covered = jnp.maximum(sol, cov_nbr)
    return covered, deg0 > 0


def mds_candidates(state) -> jax.Array:
    """MDS candidate rule: a node is actionable iff it is not in S and its
    closed neighborhood still contains an undominated positive-degree
    node.  Degree-0 nodes have empty gain, so padding can never enter —
    the contract :func:`ensure_padding_safe` verifies."""
    covered, need = _covered_and_need(state)
    uncov = (need & (covered < 0.5)).astype(jnp.float32)
    if isinstance(state, CsrGraphState):
        rid = csr_row_ids(state.indptr, state.indices.shape[1])
        em = state.edge_mask.astype(jnp.float32)
        u_pad = jnp.pad(uncov, ((0, 0), (0, 1)))
        u_col = jax.vmap(lambda ub, ib: ub[ib])(u_pad, state.indices)
        gain = uncov + csr_segment_sum(em * u_col, rid, uncov.shape[1])
    elif isinstance(state, SparseGraphState):
        val = state.valid.astype(jnp.float32)
        u_pad = jnp.pad(uncov, ((0, 0), (0, 1)))
        u_nbr = jax.vmap(lambda ub, nb: ub[nb])(u_pad, state.neighbors)
        gain = uncov + (val * u_nbr).sum(-1)
    else:
        gain = uncov + state.matvec(uncov)
    return ((state.solution < 0.5) & (gain > 0)).astype(jnp.float32)


def cover_commit(state, sel: jax.Array):
    """Closed-neighborhood-cover commit (MDS): S gains ``sel``; candidates
    re-derive from the updated coverage; done when every positive-degree
    node is dominated (⟺ no candidate has positive gain)."""
    solution = jnp.maximum(state.solution, sel)
    new = dataclasses.replace(state, solution=solution)
    candidate = mds_candidates(new)
    done = candidate.sum(-1) == 0
    return dataclasses.replace(new, candidate=candidate), done


@register("mds", residual=False, commit=cover_commit,
          candidates=mds_candidates,
          checker=lambda adj0, sol: is_dominating_set(adj0, sol),
          sense="min")
def mds_step(state, action: jax.Array):
    """Minimum Dominating Set step: adding node v to S dominates v's
    closed neighborhood; reward is -1 per selected node (minimize |S|);
    done when every positive-degree node is dominated (isolated nodes are
    padding by convention and never need domination).

    Non-candidate actions (already-done rows in a mixed-length training
    batch) commit nothing and earn 0 instead of a spurious -1."""
    b, n = state.candidate.shape
    sel = _onehot(action, n) * state.candidate
    new_state, done = cover_commit(state, sel)
    reward = -sel.sum(-1)
    return new_state, reward, done


def reset(adj) -> GraphState:
    return init_state(adj)


def solution_size(state) -> jax.Array:
    return state.solution.sum(-1)


def is_cover(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """Check the MVC invariant: every original edge touches a solution node."""
    keep = 1.0 - solution
    uncovered = adj0 * keep[..., :, None] * keep[..., None, :]
    return uncovered.sum((-1, -2)) == 0


def is_cover_sparse(neighbors: jax.Array, valid: jax.Array,
                    solution: jax.Array) -> jax.Array:
    """Sparse-representation MVC invariant: no residual edge survives S."""
    return residual_edge_mask(neighbors, valid, solution).sum((-1, -2)) == 0


def is_independent_set(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """MIS invariant: no original edge has both endpoints in S."""
    inside = adj0 * solution[..., :, None] * solution[..., None, :]
    return inside.sum((-1, -2)) == 0


def is_dominating_set(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """MDS invariant under the padding convention: every POSITIVE-degree
    node is in S or adjacent to a node in S (isolated nodes are padding
    and count as already dominated — see ``ensure_padding_safe``)."""
    deg = adj0.sum(-1)
    cov_nbr = jnp.einsum("...nm,...m->...n", adj0, solution)
    covered = jnp.maximum(solution, (cov_nbr > 0).astype(solution.dtype))
    return (((deg > 0) & (covered < 0.5)).sum(-1)) == 0


def cut_value(adj0: jax.Array, solution: jax.Array) -> jax.Array:
    """MaxCut objective: number of original edges with exactly one endpoint
    in S (each cut edge counted once from the S side)."""
    outside = 1.0 - solution
    return (adj0 * solution[..., :, None] * outside[..., None, :]
            ).sum((-1, -2))
