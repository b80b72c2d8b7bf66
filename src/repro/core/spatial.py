"""Spatially-partitioned policy evaluation and GD on the 2-D ``(data,
graph)`` mesh (paper §4.1 generalized; DESIGN.md §3/§10).

The mesh/partitioning layer itself lives in :mod:`repro.core.mesh` — this
module holds the shard_map computations that run on it:

``spatial_scores_fn`` is the paper's Alg. 2 + Alg. 3 + Alg. 4 lines 4-6
under ``jax.shard_map``: each device holds a (B/dp, N/sp, N) tile of
adjacency rows and (B/dp, N/sp) mask slices, computes local scores with
per-layer collectives over the ``graph`` axis only (each data slice is an
independent graph batch), and the all-gather returns the full (B/dp, N)
score block replicated over ``graph``.

``sparse_spatial_scores_fn`` is the same algorithm on the paper's
DISTRIBUTED SPARSE GRAPH STORAGE (§4.1, §5.2): each device holds the
(B/dp, N/sp, D) padded neighbor-list rows of its resident nodes —
O(N·maxdeg/sp) per device instead of O(N²/sp) — plus local C/S mask
slices.  Per embedding layer the (B/dp, K, N) embedding buffer is
all-gathered over ``graph`` so local gathers can reach remote-resident
neighbors (DESIGN.md §3).

``dense_solve_eval_fn`` is one evaluation of the fused dense solve on
the paper's distributed storage (§5.3): the adjacency rows stay split
over ``graph`` for the whole solve, and scoring, top-d selection and the
commit all run under one shard_map on the resident rows (the commit
rewrites only them).  ``spatial_solve_scores_fn`` is the sparse rep's
per-evaluation scorer for the same loop.

``spatial_train_minibatch_fn`` is Alg. 5's per-GPU gradient descent with
the MPI_All_reduce generalized to the 2-D mesh: every (data, graph) tile
owns the TD-error terms of its local batch rows whose action node resides
in its row block, and gradients are ``lax.psum``-ed over BOTH axes.

Legacy entry point: ``make_graph_mesh(P)`` returns the ``(1, P)`` mesh —
the paper's original 1-D node sharding is the dp=1 column of the 2-D
layout, so every pre-mesh caller keeps working unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import (DATA, GRAPH, DATASET_SPEC, DENSE_SOLVE_SPECS,
                   DENSE_STATE_SPECS, SPARSE_STATE_SPECS, SCORES_SPEC,
                   TUPLE_SPEC, make_mesh, mesh_shape, per_device_bytes,
                   sparse_per_device_bytes, state_field_specs)  # noqa: F401
from .policy import PolicyParams, policy_scores
from .qmodel import scores_local
from .s2v_sparse import (closed_keep_local, edge_factors,
                         embed_sparse_local, residual_edge_factors,
                         sparse_local_scores)

AXIS = GRAPH     # node-sharding axis name used by the per-layer collectives


def make_graph_mesh(p: Optional[int] = None) -> jax.sharding.Mesh:
    """Legacy 1-D entry point: P-way node sharding == the (1, P) mesh."""
    return make_mesh(1, p)


def _check_divisible(mesh, b: int, n: int, what: str) -> None:
    dp, sp = mesh_shape(mesh)
    if b % dp:
        raise ValueError(f"{what}: batch {b} not divisible by data-axis "
                         f"size {dp} of mesh {mesh_shape(mesh)}")
    if n % sp:
        raise ValueError(f"{what}: {n} node rows not divisible by "
                         f"graph-axis size {sp} of mesh {mesh_shape(mesh)}")


def spatial_scores_fn(mesh: jax.sharding.Mesh, num_layers: int, *,
                      kernel: str = "fused", compute: str = "f32"):
    """Build the mesh-partitioned scorer (dense representation).

    in:  adj (B, N, N), sol (B, N), cand (B, N)   [batch sharded over
         ``data``, node rows over ``graph``]
    out: scores (B, N), replicated over ``graph`` (post all-gather,
         Alg. 4 line 6), batch still sharded over ``data``.
    """

    from ..sharding.compat import shard_map_nocheck

    @functools.partial(
        shard_map_nocheck, mesh=mesh,
        in_specs=(P(),) + DENSE_STATE_SPECS,
        out_specs=SCORES_SPEC,
        # all_gather output is value-identical on every device of a graph
        # group (Alg. 4 line 6); VMA/rep inference can't prove that
        # statically → disable check.
    )
    def scorer(params: PolicyParams, adj_l, sol_l, cand_l):
        local = policy_scores(params, adj_l, sol_l, cand_l,
                              num_layers=num_layers, axis=AXIS,
                              kernel=kernel, compute=compute)
        # Alg. 4 line 6: MPI_All_gather of the (B/dp, N/sp) local scores.
        gathered = lax.all_gather(local, AXIS, axis=1, tiled=True)
        return gathered

    def fn(params, adj, sol, cand):
        _check_divisible(mesh, adj.shape[0], adj.shape[1], "dense scores")
        return scorer(params, adj, sol, cand)

    return fn


def sparse_spatial_scores_fn(mesh: jax.sharding.Mesh, num_layers: int,
                             gather_impl=None, *, residual=True,
                             kernel: str = "fused", compute: str = "f32"):
    """Build the mesh-partitioned scorer on distributed sparse storage.

    in:  neighbors (B, N, D) int32, valid (B, N, D) bool, sol (B, N),
         cand (B, N)   [batch sharded over ``data``; the node axis over
         ``graph``: each device holds the (B/dp, N/sp, D) neighbor-list
         rows of its resident nodes]
    out: scores (B, N), replicated over ``graph``, batch over ``data``.

    ``residual`` is the env's topology mode (``env.register``):
    ``False``/``"none"`` scores the ORIGINAL topology (MaxCut/MDS —
    committing a node deletes no edges), skipping the solution-mask
    all-gather the residual-edge factors need; ``"closed"`` removes S and
    its neighbors (MIS — one extra (B, N) keep all-gather over ``graph``).
    """

    from ..sharding.compat import shard_map_nocheck

    @functools.partial(
        shard_map_nocheck, mesh=mesh,
        in_specs=(P(),) + SPARSE_STATE_SPECS,
        out_specs=SCORES_SPEC,
    )
    def scorer(params: PolicyParams, nbr_l, valid_l, sol_l, cand_l):
        # Edge factors need keep[] of REMOTE neighbor endpoints (paper
        # §5.1's C/S broadcast) — the shared helper all-gathers the local
        # S (and, for "closed", keep) slices over the graph axis.
        local = sparse_local_scores(params, nbr_l, valid_l, sol_l, cand_l,
                                    num_layers=num_layers, residual=residual,
                                    axis=AXIS, kernel=kernel,
                                    compute=compute, gather_impl=gather_impl)
        return lax.all_gather(local, AXIS, axis=1, tiled=True)

    def fn(params, nbr, valid, sol, cand):
        _check_divisible(mesh, nbr.shape[0], nbr.shape[1], "sparse scores")
        return scorer(params, nbr, valid, sol, cand)

    return fn


def spatial_solve_scores_fn(mesh: jax.sharding.Mesh, *, num_layers: int,
                            rep, residual=True, kernel: str = "fused",
                            compute: str = "f32"):
    """State-in, scores-out wrapper around the sparse mesh-partitioned
    scorer for the FUSED solve loop (DESIGN.md §9): takes the solve state
    (batch sharded over ``data`` by the engine), reshards its neighbor
    lists onto the mesh's (data, graph) tiling inside jit, runs one
    spatially-partitioned policy evaluation, and returns the all-gathered
    (B, N) scores replicated over ``graph`` so the top-d commit runs in
    the paper's Fig. 4 lockstep.  The dense rep keeps its rows split for
    the whole solve instead (:func:`dense_solve_eval_fn`).
    """
    if rep.name != "sparse":
        raise ValueError(f"spatial_solve_scores_fn scores the sparse rep; "
                         f"got {rep.name!r}")
    scorer = sparse_spatial_scores_fn(mesh, num_layers, residual=residual,
                                      kernel=kernel, compute=compute)
    return lambda params, state: scorer(params, state.neighbors,
                                        state.valid, state.solution,
                                        state.candidate)


def dense_solve_eval_fn(mesh: jax.sharding.Mesh, *, problem: str,
                        num_layers: int, use_adaptive: bool, max_d: int,
                        kernel: str = "fused", compute: str = "f32"):
    """One policy evaluation of the fused solve on a dense state whose
    adjacency rows stay split over ``graph`` (paper §5.1 spatial
    parallelism on §5.3 distributed storage; DESIGN.md §10).

    Returns ``fn(params, state) -> (state', done, committed)`` on the
    carry's layout (``mesh.DENSE_SOLVE_SPECS``): each device holds the
    (B/dp, N/sp, N) rows of its nodes and the whole (B/dp, N) masks.  Under
    one ``shard_map`` it scores its rows (one (B, K, N) ``psum`` per layer
    over ``graph``), gathers the (B, N) scores so every device of a graph
    group takes the same top-d, and runs the env's selection and commit
    rules on a state whose ``row_axis`` is ``graph``: the commit rewrites
    only the resident rows, and the done check reduces over ``graph``."""
    from ..sharding.compat import shard_map_nocheck
    from .graphrep import DENSE
    from .graphs import GraphState
    from .inference import apply_selection

    specs = GraphState(**DENSE_SOLVE_SPECS)

    @functools.partial(shard_map_nocheck, mesh=mesh, in_specs=(P(), specs),
                       out_specs=(specs, P(DATA), P(DATA)))
    def evaluate(params, state):
        state = dataclasses.replace(state, row_axis=AXIS)
        scores = DENSE.scores(params, state, num_layers=num_layers,
                              kernel=kernel, compute=compute)
        new, done, committed = apply_selection(state, scores,
                                               state.candidate, use_adaptive,
                                               problem, max_d)
        return dataclasses.replace(new, row_axis=None), done, committed

    def fn(params, state):
        _check_divisible(mesh, state.adj.shape[0], state.adj.shape[1],
                         "dense solve")
        return evaluate(params, state)

    return fn


def _ownership_loss(s_l, action, target, my, nl, dp):
    """Squared TD error of the locally-owned (batch row, action node)
    terms, normalized by the GLOBAL minibatch size (local rows × dp) so
    the psum over both mesh axes reproduces the single-device mean."""
    loc = action - my * nl
    owned = (loc >= 0) & (loc < nl)
    qsa = jnp.take_along_axis(
        s_l, jnp.clip(loc, 0, nl - 1)[:, None], axis=-1)[:, 0]
    sq = jnp.where(owned, jnp.square(qsa - target), 0.0)
    return sq.sum() / (action.shape[0] * dp)


def spatial_train_minibatch_fn(mesh: jax.sharding.Mesh, *,
                               num_layers: int, lr: float, jit: bool = True,
                               kernel: str = "fused", compute: str = "f32"):
    """Build the mesh-parallel GD step (paper Alg. 5's per-GPU gradient
    descent + MPI_All_reduce, generalized to the 2-D mesh; DESIGN.md
    §8/§10) — the GSPMD-partitioned REFERENCE path
    (``collectives="gspmd"``; the default on full 2-D meshes is
    :func:`manual_train_minibatch_fn`).

    Returns ``fn(params, opt, state, action, target) -> (params, opt,
    loss)`` — a drop-in for the single-device ``_train_minibatch``: the TD
    loss/grad of the minibatch runs under ``shard_map`` on the
    (B/dp, N/sp, ·) tiled layout.  Each (data, graph) mesh tile owns the
    squared-error terms of its LOCAL batch rows whose action node resides
    in its node-row block, evaluates them from spatially-partitioned
    policy scores (per-layer collectives over ``graph``, as in the
    inference path), and loss and gradients are ``lax.psum``-ed over BOTH
    axes before one replicated Adam update.  Dispatches on the state's
    representation (dense ``GraphState`` / ``SparseGraphState``) and its
    ``residual`` semantics.  B must divide by dp and N by sp.
    """
    from functools import partial
    from ..optim import adam_update
    from ..sharding.compat import shard_map_nocheck
    from .graphs import SparseGraphState

    BOTH = (DATA, GRAPH)
    dp, _sp = mesh_shape(mesh)

    def _build_dense():
        @partial(shard_map_nocheck, mesh=mesh,
                 in_specs=(P(),) + DENSE_STATE_SPECS
                 + (TUPLE_SPEC, TUPLE_SPEC),
                 out_specs=(P(), P()))
        def grad_fn(params, adj_l, sol_l, cand_l, action, target):
            nl = adj_l.shape[1]
            my = lax.axis_index(AXIS)

            def loss_fn(p):
                s_l = policy_scores(p, adj_l, sol_l, cand_l,
                                    num_layers=num_layers, axis=AXIS,
                                    masked=False, kernel=kernel,
                                    compute=compute)
                return _ownership_loss(s_l, action, target, my, nl, dp)

            loss_l, grads_l = jax.value_and_grad(loss_fn)(params)
            # Alg. 5: MPI_All_reduce of the (4K²+4K)-parameter gradient —
            # over the node shards AND the batch shards.
            grads = jax.tree.map(lambda g: lax.psum(g, BOTH), grads_l)
            return lax.psum(loss_l, BOTH), grads

        return grad_fn

    def _build_sparse(residual: bool):
        @partial(shard_map_nocheck, mesh=mesh,
                 in_specs=(P(),) + SPARSE_STATE_SPECS
                 + (TUPLE_SPEC, TUPLE_SPEC),
                 out_specs=(P(), P()))
        def grad_fn(params, nbr_l, val_l, sol_l, cand_l, action, target):
            nl = nbr_l.shape[1]
            my = lax.axis_index(AXIS)

            def loss_fn(p):
                s_l = sparse_local_scores(p, nbr_l, val_l, sol_l, cand_l,
                                          num_layers=num_layers,
                                          residual=residual, axis=AXIS,
                                          masked=False, kernel=kernel,
                                          compute=compute)
                return _ownership_loss(s_l, action, target, my, nl, dp)

            loss_l, grads_l = jax.value_and_grad(loss_fn)(params)
            grads = jax.tree.map(lambda g: lax.psum(g, BOTH), grads_l)
            return lax.psum(loss_l, BOTH), grads

        return grad_fn

    built = {}

    def fn(params, opt, state, action, target):
        _check_divisible(mesh, state.candidate.shape[0],
                         state.candidate.shape[1], "spatial GD")
        if isinstance(state, SparseGraphState):
            key = ("sparse", state.residual)
            if key not in built:
                built[key] = _build_sparse(state.residual)
            loss, grads = built[key](params, state.neighbors, state.valid,
                                     state.solution, state.candidate,
                                     action, target)
        else:
            key = ("dense",)
            if key not in built:
                built[key] = _build_dense()
            loss, grads = built[key](params, state.adj, state.solution,
                                     state.candidate, action, target)
        params, opt = adam_update(params, grads, opt, lr=lr)
        return params, opt, loss

    return jax.jit(fn) if jit else fn


def manual_train_minibatch_fn(mesh: jax.sharding.Mesh, *, rep,
                              num_layers: int, lr: float, gamma: float,
                              minibatch: int, residual=True,
                              target_mode: str = "fresh",
                              kernel: str = "fused", compute: str = "f32",
                              jit: bool = True):
    """Build the MANUAL-COLLECTIVE mesh GD step (``collectives="manual"``,
    DESIGN.md §10) — the default on full 2-D meshes.

    Unlike the gspmd reference path, which hands GSPMD the assembled
    minibatch to partition, no operand is ever replicated: the replay ring stays resident in its (R/dp, N/sp, ·)
    tiling, the minibatch sample indices are the ONLY replicated input
    (4·B bytes), and everything the math needs from remote shards moves
    through hand-written lax collectives:

    ==================  =========  =============================  ==========
    collective          axis       operand                        payload/dev
    ==================  =========  =============================  ==========
    lax.psum_scatter    data       sampled replay rows (the       4·B·N/(dp·sp)
                                   masked-contribution exchange   per field
                                   below)
    lax.all_gather      graph      solution / keep factors for    4·B·N/dp
                                   the residual-topology remat
    lax.all_gather      graph      per-layer (B, K, Nl) embeds    4·B·K·N/dp
                                   (inside embed_local, per eval)
    lax.pmax/psum       graph      fresh-target max-Q / has-cand  4·B/dp
    lax.psum            both       loss + (4K²+4K) gradient       16K²+16K+4
    ==================  =========  =============================  ==========

    Replay-row exchange: every device computes the SAME (B,) sample
    indices (the bit-exact :func:`device_replay_sample` stream), gathers
    the hits among its own R/dp resident rows, zero-masks the misses and
    ``psum_scatter``-s over ``data`` — each data shard ends up holding
    exactly its (B/dp, ·) minibatch tile, node columns still tiled over
    ``graph``.  Topology is re-materialized per tile from the
    ``P(None, graph, None)``-sharded dataset with the same mode logic as
    ``GraphRep.state_from_tuples`` (graph-axis all_gathers supply remote
    solution columns), so the two train paths agree to float exactness.

    Returns ``fn(params, opt, replay, source, key) -> (params, opt,
    loss)``.  ``source`` is the dataset in ``rep``'s layout (dense
    (G, N, N) array or ``SparseGraphBatch``), constrained to
    ``DATASET_SPEC`` by the engine.  ``candidate_fn`` envs (mds) are not
    supported — the engine falls back to the gspmd reference path.
    """
    from functools import partial
    from ..optim import adam_update
    from ..sharding.compat import shard_map_nocheck
    from .env import normalize_residual_mode
    from .replay import device_replay_sample_idx

    rep_name = getattr(rep, "name", rep)
    if rep_name not in ("dense", "sparse"):
        raise ValueError(f"manual collectives support the dense/sparse "
                         f"reps, got {rep_name!r}")
    dense = rep_name == "dense"
    mode = normalize_residual_mode(residual)
    stored = target_mode == "stored"
    BOTH = (DATA, GRAPH)
    dp, _sp = mesh_shape(mesh)

    def _exchange(idx, f_l, dtype):
        """rows[idx] of a P(data, ...)-tiled replay field, returned as the
        (B/dp, ...) minibatch tile of THIS data shard: each shard gathers
        the sampled rows it owns, zero-masks the rest, and psum_scatter
        sums the one-hot contributions while scattering the batch dim."""
        md = lax.axis_index(DATA)
        per = f_l.shape[0]
        owner = idx // per
        safe = jnp.clip(idx - md * per, 0, per - 1)
        rows = f_l[safe].astype(dtype)
        mine = (owner == md).reshape((-1,) + (1,) * (rows.ndim - 1))
        return lax.psum_scatter(jnp.where(mine, rows, 0), DATA,
                                scatter_dimension=0, tiled=True)

    def _dense_remat(src_l, gi_t, sol_t):
        """DenseRep.state_from_tuples on the tile: (B/dp, N/sp, N) local
        node rows, full columns (the dataset is sharded over rows only)."""
        base = src_l[0][gi_t]
        if mode == "solution":
            keep_l = 1.0 - sol_t
            keep_f = lax.all_gather(keep_l, GRAPH, axis=1, tiled=True)
            adj = base * keep_l[:, :, None] * keep_f[:, None, :]
            cand = ((adj.sum(-1) > 0) & (sol_t < 0.5)).astype(jnp.float32)
        elif mode == "none":
            adj = base
            cand = ((base.sum(-1) > 0) & (sol_t < 0.5)).astype(jnp.float32)
        else:                                # closed: drop S and N(S)
            sol_f = lax.all_gather(sol_t, GRAPH, axis=1, tiled=True)
            nbr_s = jnp.einsum("bnm,bm->bn", base, sol_f)
            keep_l = (1.0 - sol_t) * (1.0 - (nbr_s > 0).astype(jnp.float32))
            keep_f = lax.all_gather(keep_l, GRAPH, axis=1, tiled=True)
            adj = base * keep_l[:, :, None] * keep_f[:, None, :]
            cand = ((base.sum(-1) > 0) & (keep_l > 0.5)).astype(jnp.float32)
        return (adj,), cand

    def _sparse_remat(src_l, gi_t, sol_t):
        """SparseRep.state_from_tuples on the tile: local (B/dp, N/sp, D)
        neighbor rows; edge factors via the shared axis-aware helpers."""
        nbr_t, val_t = src_l[0][gi_t], src_l[1][gi_t]
        edge = edge_factors(nbr_t, val_t, sol_t,
                            True if mode == "solution"
                            else False if mode == "none" else mode,
                            axis=AXIS)
        if mode == "solution":
            cand = ((edge.sum(-1) > 0) & (sol_t < 0.5)).astype(jnp.float32)
        elif mode == "none":
            cand = ((val_t.sum(-1) > 0) & (sol_t < 0.5)).astype(jnp.float32)
        else:
            keep_l = closed_keep_local(nbr_t, val_t, sol_t, axis=AXIS)
            cand = ((val_t.sum(-1) > 0) & (keep_l > 0.5)).astype(jnp.float32)
        return (nbr_t, val_t, edge), cand

    def _scores(p, topo_t, sol_t, cand_t, masked):
        if dense:
            return policy_scores(p, topo_t[0], sol_t, cand_t,
                                 num_layers=num_layers, axis=AXIS,
                                 masked=masked, kernel=kernel,
                                 compute=compute)
        nbr_t, _val_t, edge_t = topo_t
        with jax.named_scope("s2v.embed"):
            emb = embed_sparse_local(p.em, nbr_t, edge_t, sol_t,
                                     num_layers=num_layers, axis=AXIS,
                                     kernel=kernel, compute=compute)
        with jax.named_scope("q.head"):
            return scores_local(p.q, emb, cand_t, axis=AXIS, masked=masked)

    remat = _dense_remat if dense else _sparse_remat
    replay_specs = (TUPLE_SPEC, P(DATA, GRAPH), TUPLE_SPEC, TUPLE_SPEC,
                    TUPLE_SPEC, P(DATA, GRAPH), TUPLE_SPEC)
    src_specs = (DATASET_SPEC,) if dense else (DATASET_SPEC, DATASET_SPEC)

    @partial(shard_map_nocheck, mesh=mesh,
             in_specs=(P(),) + replay_specs + src_specs + (P(),),
             out_specs=(P(), P()))
    def grad_fn(params, gi_l, sol_l, act_l, tgt_l, rew_l, sol2_l, dn_l,
                *src_and_idx):
        *src_l, idx = src_and_idx
        my = lax.axis_index(AXIS)

        gi_t = _exchange(idx, gi_l, jnp.int32)
        sol_t = _exchange(idx, sol_l, jnp.float32)
        act_t = _exchange(idx, act_l, jnp.int32)
        topo_t, cand_t = remat(src_l, gi_t, sol_t)
        nl = sol_t.shape[1]

        if stored:
            tgt_t = _exchange(idx, tgt_l, jnp.float32)
        else:
            # Fresh TD target (DESIGN.md §7) — constant wrt the params
            # being differentiated, so computed OUTSIDE value_and_grad
            # exactly like the engine's max_q_raw call.
            rew_t = _exchange(idx, rew_l, jnp.float32)
            sol2_t = _exchange(idx, sol2_l, jnp.float32)
            dn_t = _exchange(idx, dn_l, jnp.float32)
            topo2_t, cand2_t = remat(src_l, gi_t, sol2_t)
            s2 = _scores(params, topo2_t, sol2_t, cand2_t, True)
            best = lax.pmax(s2.max(-1), AXIS)
            has = lax.psum(cand2_t.sum(-1), AXIS) > 0
            nxt = jnp.where(has, best, 0.0)
            tgt_t = rew_t + gamma * nxt * (1.0 - dn_t)

        def loss_fn(p):
            s_l = _scores(p, topo_t, sol_t, cand_t, False)
            return _ownership_loss(s_l, act_t, tgt_t, my, nl, dp)

        loss_l, grads_l = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: lax.psum(g, BOTH), grads_l)
        return lax.psum(loss_l, BOTH), grads

    _idx_sharding = jax.sharding.NamedSharding(mesh, P())

    def fn(params, opt, replay, source, key):
        cap, n = replay.solution.shape[-2:]
        if cap % dp:
            raise ValueError(f"replay capacity {cap} not divisible by the "
                             f"data-axis size {dp} of the mesh")
        _check_divisible(mesh, minibatch, n, "manual GD")
        idx = device_replay_sample_idx(replay, key, minibatch)
        idx = jax.lax.with_sharding_constraint(idx, _idx_sharding)
        src = (source,) if dense else (source.neighbors, source.valid)
        loss, grads = grad_fn(params, replay.graph_idx, replay.solution,
                              replay.action, replay.target, replay.reward,
                              replay.next_solution, replay.done, *src, idx)
        params, opt = adam_update(params, grads, opt, lr=lr)
        return params, opt, loss

    return jax.jit(fn) if jit else fn


def shard_graph_arrays(mesh, adj, sol, cand):
    """Place (B,N,N)/(B,N)/(B,N) arrays with the mesh partitioning: batch
    over ``data``, node rows over ``graph`` (the paper's row layout)."""
    ns = jax.sharding.NamedSharding
    a_spec, s_spec, c_spec = DENSE_STATE_SPECS
    adj = jax.device_put(adj, ns(mesh, a_spec))
    sol = jax.device_put(sol, ns(mesh, s_spec))
    cand = jax.device_put(cand, ns(mesh, c_spec))
    return adj, sol, cand


def shard_sparse_arrays(mesh, neighbors, valid, sol, cand):
    """Place the sparse state with the mesh partitioning: each device
    receives the (B/dp, N/sp, D) neighbor-list block of its resident
    nodes."""
    ns = jax.sharding.NamedSharding
    n_spec, v_spec, s_spec, c_spec = SPARSE_STATE_SPECS
    neighbors = jax.device_put(neighbors, ns(mesh, n_spec))
    valid = jax.device_put(valid, ns(mesh, v_spec))
    sol = jax.device_put(sol, ns(mesh, s_spec))
    cand = jax.device_put(cand, ns(mesh, c_spec))
    return neighbors, valid, sol, cand
