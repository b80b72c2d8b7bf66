"""Parallel RL inference (paper Alg. 4) + adaptive multiple-node selection
(paper §4.5.1), representation- and environment-polymorphic.

``solve`` drives a batch of B graphs to complete solutions using the
(pre)trained policy, on ANY GraphRep backend — the dense (B, N, N)
adjacency path, the sparse (B, N, D) padded neighbor-list path, or the
flat CSR edge-array path (``rep="dense"|"sparse"|"csr"``, see DESIGN.md
§1/§13) — for ANY registered environment (``problem="mvc"|"maxcut"|
"mis"|"mds"`` — the selection/commit/termination rules come from the env
registry, DESIGN.md §9/§11).
Each iteration is one policy evaluation; with the adaptive schedule, up to
d ∈ {max_d, max_d/2, max_d/4, max_d/8} top-scoring candidates are
committed per evaluation, with d shrinking as the candidate set shrinks
(``max_d`` defaults to the paper's 8; paper-scale solves on million-node
graphs raise it so a solve stays tens of evaluations, §4.5.1):

    |C| >  N/2        -> d = max_d
    |C| in (N/4, N/2] -> d = max_d/2
    |C| in (N/8, N/4] -> d = max_d/4
    |C| <= N/8        -> d = max_d/8  (each tier floored at 1)

Two execution engines, selected like the training engine (DESIGN.md §8/§9):

- ``engine="device"`` (default) — the FUSED solve: the whole score →
  top-d commit → done-check loop is one jitted ``lax.while_loop``
  (``repro.core.engine.get_solve_step``) with a single host↔device
  round-trip per solve, optionally under the P-way spatial shard_map path
  (``spatial=P``).
- ``engine="host"`` — the reference loop: one jitted step per policy
  evaluation with a blocking ``done`` fetch after each (the paper's
  host-driven driver); the fused path is tested bit-identical against it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import env as env_lib
from .graphs import CsrGraphState, SparseGraphState
from .graphrep import GraphRep, get_rep
from .policy import PolicyConfig, PolicyParams
from .qmodel import NEG_INF

MAX_D = 8


def adaptive_d(num_candidates: jax.Array, n: int,
               max_d: int = MAX_D) -> jax.Array:
    """Per-graph d from the paper's schedule (exactly 8/4/2/1 at the
    default ``max_d=8``). num_candidates: (B,)."""
    c = num_candidates
    return jnp.where(c > n / 2, max_d,
           jnp.where(c > n / 4, max(max_d // 2, 1),
           jnp.where(c > n / 8, max(max_d // 4, 1),
                     max(max_d // 8, 1)))).astype(jnp.int32)


def select_top_d(scores: jax.Array, candidate: jax.Array,
                 use_adaptive: bool,
                 max_d: int = MAX_D) -> Tuple[jax.Array, jax.Array]:
    """Alg. 4 lines 5-7: top-d selection mask from masked scores.

    Returns ``(sel, ncommit)``: the (B, N) union-of-one-hots commit mask
    and the (B,) per-graph commit count.  Finished graphs (no candidates →
    all scores NEG_INF) select nothing.  Shared verbatim by the host-loop
    step and the fused while_loop body so the two engines stay
    bit-identical.
    """
    b, n = candidate.shape
    top_scores, top_idx = jax.lax.top_k(scores, min(max_d, n))  # (B, max_d)
    ncand = candidate.sum(-1)
    d = (adaptive_d(ncand, n, max_d) if use_adaptive
         else jnp.ones((b,), jnp.int32))
    rank = jnp.arange(top_idx.shape[1])[None, :]
    valid = (rank < d[:, None]) & (top_scores > NEG_INF / 2)
    sel = jnp.zeros((b, n), jnp.float32)
    sel = sel.at[jnp.arange(b)[:, None], top_idx].max(valid.astype(jnp.float32))
    return sel, valid.sum(-1)


def apply_selection(state, scores, candidate, use_adaptive: bool,
                    problem: str, max_d: int = MAX_D):
    """Alg. 4 lines 5-9, env-polymorphic: top-d selection, the env's
    optional selection prune (MIS must thin adjacent picks out of a raw
    top-d set), and the env's commit/termination rule.  Shared verbatim by
    the host-loop step and the fused while_loop body so the two engines
    stay bit-identical per problem.  Note the MIS prune scan is capped at
    ``env._MAX_COMMIT`` kept picks per evaluation regardless of ``max_d``
    (independence filtering is inherently sequential).  Selection and
    prune run under the named scope ``env.select``, the commit under
    ``env.commit``."""
    with jax.named_scope("env.select"):
        sel, ncommit = select_top_d(scores, candidate, use_adaptive, max_d)
        prune = env_lib.prune_rule(problem)
        if prune is not None:
            sel = prune(state, sel, scores)
            ncommit = sel.sum(-1).astype(jnp.int32)
    with jax.named_scope("env.commit"):
        new_state, done = env_lib.commit_rule(problem)(state, sel)
    return new_state, done, ncommit


@functools.partial(jax.jit,
                   static_argnames=("rep", "problem", "num_layers",
                                    "use_adaptive", "kernel", "compute",
                                    "max_d"))
def _inference_step(params: PolicyParams, state, *, rep: GraphRep,
                    problem: str, num_layers: int, use_adaptive: bool,
                    kernel: str = "fused", compute: str = "f32",
                    max_d: int = MAX_D):
    """One policy evaluation + top-d commit (Alg. 4 body, vectorized over B).

    Identical on all representations: the backend supplies the scores,
    the env registry the selection/commit/termination rules; only the
    state layout differs.  Finished graphs (no candidates) commit nothing.
    """
    scores = rep.scores(params, state, num_layers=num_layers,
                        kernel=kernel, compute=compute)     # (B, N) masked
    return apply_selection(state, scores, state.candidate, use_adaptive,
                           problem, max_d)


def init_solve_state(rep: GraphRep, adj, problem: str = "mvc"):
    """Fresh solve state in ``rep``'s layout, carrying the env's residual
    mode (MaxCut/MDS on the sparse path must score the ORIGINAL topology;
    MIS scores the closed-neighborhood residual — see ``env.register``)
    and the env's candidate derivation.

    Enforces the padding-safety contract before any compute: an env whose
    candidate rule could admit degree-0 (padding) nodes is rejected here
    with an actionable error (``env.ensure_padding_safe``)."""
    env_lib.ensure_padding_safe(problem)
    state = rep.init_state(adj)
    if isinstance(state, (SparseGraphState, CsrGraphState)):
        flag = env_lib.sparse_residual_flag(problem)
        if state.residual != flag:
            state = dataclasses.replace(state, residual=flag)
    cand_fn = env_lib.candidate_rule(problem)
    if cand_fn is not None:
        state = dataclasses.replace(state, candidate=cand_fn(state))
    return state


@dataclasses.dataclass
class InferenceResult:
    solution: np.ndarray       # (B, N) masks
    sizes: np.ndarray          # (B,) |S|
    policy_evals: int          # number of policy-model evaluations
    nodes_committed: np.ndarray


def solve(params: PolicyParams, adj0, *, num_layers: int = 2,
          multi_node: bool = False, max_evals: Optional[int] = None,
          step_fn: Optional[Callable] = None,
          rep: Union[str, GraphRep] = "dense", problem: str = "mvc",
          engine: str = "device", spatial=0, kernel: str = "fused",
          compute: str = "f32", max_d: int = MAX_D) -> InferenceResult:
    """Run Alg. 4 until every graph in the batch has a complete solution.

    multi_node=False reproduces the original d=1 algorithm; True enables the
    adaptive schedule of §4.5.1 — on both representations.  ``rep`` selects
    the graph backend ("dense" | "sparse" or a GraphRep instance);
    ``problem`` the registered environment whose commit/termination rule
    drives the loop; ``engine`` the execution engine ("device" = fused
    jitted while_loop, one host sync per solve; "host" = per-eval loop);
    ``spatial`` selects the 2-D ``(data, graph)`` mesh — ``(dp, sp)``
    shards the batch dp ways over ``data`` (B/dp graphs per device) and
    partitions every policy evaluation sp-way under shard_map; an int P
    back-compats to ``(1, P)`` (device engine only, DESIGN.md §10).
    ``step_fn`` may override the jitted step (host engine only; kept for
    custom drivers).  ``kernel``/``compute`` select the S2V layer lowering
    and matmul operand precision (DESIGN.md §12) on both engines.
    ``max_d`` widens the adaptive schedule's commit cap beyond the paper's
    8 — million-node solves set it to a few % of N so one solve is tens of
    evaluations, not ~N/8.
    """
    from .mesh import normalize_spatial
    if engine not in ("host", "device"):
        raise ValueError(f"unknown inference engine {engine!r}")
    rep = get_rep(rep)
    if engine == "device" and step_fn is None:
        return _solve_fused(params, adj0, rep=rep, problem=problem,
                            num_layers=num_layers, multi_node=multi_node,
                            max_evals=max_evals, spatial=spatial,
                            kernel=kernel, compute=compute, max_d=max_d)
    state = init_solve_state(rep, adj0, problem)
    max_evals = max_evals or (state.num_nodes + max_d)
    dp, _sp = normalize_spatial(spatial)
    if (dp, _sp) != (1, 1):
        raise ValueError("spatial solve runs on the fused path only; it is "
                         "incompatible with engine='host' and with step_fn "
                         "overrides")

    evals = 0
    committed = np.zeros((state.batch,), np.int64)
    fn = step_fn or (lambda p, s: _inference_step(
        p, s, rep=rep, problem=problem, num_layers=num_layers,
        use_adaptive=multi_node, kernel=kernel, compute=compute,
        max_d=max_d))
    for _ in range(max_evals):
        state, done, ncommit = fn(params, state)
        evals += 1
        committed += np.asarray(ncommit)
        if bool(np.asarray(done).all()):
            break
    sol = np.asarray(state.solution)
    return InferenceResult(solution=sol, sizes=sol.sum(-1).astype(np.int64),
                           policy_evals=evals, nodes_committed=committed)


def _solve_fused(params: PolicyParams, adj0, *, rep: GraphRep, problem: str,
                 num_layers: int, multi_node: bool,
                 max_evals: Optional[int], spatial, kernel: str,
                 compute: str, max_d: int) -> InferenceResult:
    """``solve`` on the device engine: one fused ``while_loop``
    (``engine.get_solve_step``) and one fetch of the answer, under three
    profiler spans on the host: ``solve.prepare`` (state init, step lookup,
    placement), ``solve.dispatch`` (the call into the fused program) and
    ``solve.fetch`` (the ``device_get`` of the answer)."""
    from .engine import get_solve_step
    from .mesh import (make_mesh, normalize_spatial, shard_rows,
                       shard_solve_state)
    with jax.profiler.TraceAnnotation("solve.prepare"):
        dp, sp = normalize_spatial(spatial)
        multi = (dp, sp) != (1, 1)
        # Donating the solve state is only safe when it does not alias the
        # caller's arrays: a prebuilt sparse/csr batch (or state) shares
        # its topology buffers with the state init_solve_state returns,
        # and donation would delete them out from under the caller.
        owned = {id(getattr(adj0, f)) for f in
                 ("indptr", "indices", "edge_mask", "neighbors", "valid",
                  "solution", "candidate") if getattr(adj0, f, None)
                 is not None} | {id(adj0)}
        graph = adj0
        if multi and rep.name == "dense" and hasattr(adj0, "ndim"):
            # a dense adjacency goes to the devices by rows before any work
            # on it, and is never gathered whole onto one device (a copy
            # unless the caller placed it so)
            graph = shard_rows(make_mesh(dp, sp), adj0)
        state = init_solve_state(rep, graph, problem)
        max_evals = max_evals or (state.num_nodes + max_d)
        if state.batch % dp:
            raise ValueError(f"batch {state.batch} not divisible by the "
                             f"data-axis size {dp} of mesh spec {spatial!r}")
        shares = any(id(x) in owned for x in jax.tree.leaves(state))
        fused = get_solve_step(rep=rep, problem=problem,
                               num_layers=num_layers,
                               use_adaptive=multi_node, spatial=spatial,
                               kernel=kernel, compute=compute, max_d=max_d,
                               donate=not shares)
        if multi:
            # the while_loop's resident layout up front, so the donated
            # state buffers alias from call one
            state = shard_solve_state(make_mesh(dp, sp), state)
    with jax.profiler.TraceAnnotation("solve.dispatch"):
        out, evals, committed = fused(params, state,
                                      jnp.asarray(max_evals, jnp.int32))
    # the solve's single host↔device round-trip: one result fetch
    with jax.profiler.TraceAnnotation("solve.fetch"):
        sol, evals, committed = jax.device_get(
            (out.solution, evals, committed))
    return InferenceResult(solution=sol, sizes=sol.sum(-1).astype(np.int64),
                           policy_evals=int(evals),
                           nodes_committed=committed.astype(np.int64))


def best_trajectory_cut(params: PolicyParams, adj0, *, num_layers: int = 2,
                        multi_node: bool = True) -> np.ndarray:
    """(B,) best MaxCut value along the RL commit trajectory.

    The maxcut env terminates when no candidate remains — every
    positive-degree node eventually joins S, so the FINAL assignment's cut
    is trivially 0 and quality lives in the trajectory.  Runs the
    host-driven loop (the fused engine returns only the final state) and
    records the cut after every commit."""
    from . import env as env_lib
    adj0 = np.asarray(adj0, np.float32)
    ja = jnp.asarray(adj0)
    best = np.zeros(adj0.shape[0])

    def recording_step(p, s):
        out = _inference_step(p, s, rep=get_rep("dense"), problem="maxcut",
                              num_layers=num_layers,
                              use_adaptive=multi_node)
        np.maximum(best, np.asarray(env_lib.cut_value(ja, out[0].solution)),
                   out=best)
        return out

    solve(params, adj0, num_layers=num_layers, problem="maxcut",
          engine="host", step_fn=recording_step)
    return best


def solve_with_config(params: PolicyParams, adj0, cfg: PolicyConfig, *,
                      multi_node: bool = False, problem: str = "mvc",
                      **kw) -> InferenceResult:
    """``solve`` with rep/engine/spatial/num_layers/kernel/compute taken
    from a :class:`PolicyConfig` — the same config-driven selection the
    training engine uses (DESIGN.md §8/§9)."""
    return solve(params, adj0, num_layers=cfg.num_layers,
                 rep=cfg.graph_rep, engine=cfg.engine, spatial=cfg.spatial,
                 kernel=cfg.kernel, compute=cfg.compute,
                 multi_node=multi_node, problem=problem, **kw)
