"""Device-resident engines: parallel training (paper Alg. 5 as ONE jitted
step) and parallel inference (paper Alg. 4 as ONE jitted while_loop).

The host training loop performs, per env step: an acting sync, a remember
sync (plus a stored-target bootstrap), and a blocking ``float(loss)`` on
every one of the τ GD iterations — 3+τ host↔device round-trips.  The fused
step runs the whole cycle on device in a single jitted call (DESIGN.md §8):

1. epsilon-greedy acting — ``jax.random`` Bernoulli over rows plus a masked
   categorical draw from each row's candidate set (Alg. 1 lines 9-10),
2. the env transition (functional, already on device),
3. TD-target computation at insertion time (Alg. 5 line 12, ``stored``
   mode) or deferred bootstrapping (``fresh`` mode, DESIGN.md §7),
4. replay insertion into the functional :class:`~repro.core.replay.DeviceReplay`
   ring buffer,
5. a ``lax.scan`` over τ GD iterations (§4.5.2) whose body samples the
   buffer, re-materializes states with Tuples2Graphs
   (``GraphRep.state_from_tuples``, Alg. 5 line 21) and applies one Adam
   update — optionally under the 2-D ``(data, graph)`` mesh
   (``spatial_train_minibatch_fn``): minibatch rows sharded over ``data``,
   node rows over ``graph``, loss/gradients psum-ed over BOTH axes
   (Alg. 5's P-GPU lockstep generalized, DESIGN.md §10).

Everything is representation-polymorphic: both GraphRep backends and both
target modes flow through the same step.  ``train_agent`` drives episodes
over this step with one host round-trip per env step (loss + done fetch).

RNG schedule (a stable contract, relied on by the equivalence tests):
``rng, k_eps, k_pick, k_train = split(rng, 4)`` per step; GD iteration t
samples with ``split(k_train, tau)[t]`` via ``device_replay_sample``.

Inference gets the same treatment (DESIGN.md §9): the host-driven Alg. 4
driver syncs ``done`` back after EVERY policy evaluation; the fused solve
(``get_solve_step``) runs the whole score → adaptive top-d commit → done
check loop as one jitted ``lax.while_loop`` — zero per-eval round-trips,
both GraphRep backends, any registered environment's commit rule, and
optionally every evaluation spatially partitioned P-way under shard_map
(per-eval collectives unchanged from the host spatial path).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import env as env_lib
from .agent import max_q_from_scores, train_minibatch_raw
from .graphrep import GraphRep, get_rep
from .inference import apply_selection
from .mesh import (DATA, MeshSpec, constrain_batch, constrain_dataset,
                   constrain_replay, constrain_solve_state, make_mesh,
                   normalize_spatial, resolve_collectives, shard_replay)
from .policy import PolicyConfig, PolicyParams
from .qmodel import NEG_INF
from .replay import (DeviceReplay, device_replay_init, device_replay_push,
                     device_replay_sample)
from ..optim import AdamState


def per_graph_scorer(mesh, rep: GraphRep, *, num_layers: int, kernel: str,
                     compute: str):
    """``rep.scores`` for the phases of a mesh step whose node rows stay
    whole (acting, TD targets, csr scoring and GD): each device scores
    its own B/dp graphs under shard_map over ``data``.  The arithmetic is
    per graph, so the scores — and their gradients — equal the one-device
    call, and the Pallas layer kernel, which GSPMD cannot partition, runs
    per device.  A batch that does not divide dp is scored whole on every
    device."""
    from jax.sharding import PartitionSpec as P
    from ..sharding.compat import shard_map_nocheck

    def score(params, state, masked=True):
        def local(p, st):
            return rep.scores(p, st, num_layers=num_layers, masked=masked,
                              kernel=kernel, compute=compute)

        if mesh is None:
            return local(params, state)
        b = state.candidate.shape[0]
        spec = P(DATA) if b % mesh.shape[DATA] == 0 else P()
        return shard_map_nocheck(
            local, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: spec, state)),
            out_specs=spec)(params, state)

    return score


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Device-resident training carry: everything Alg. 5 mutates per step."""
    params: PolicyParams
    opt: AdamState
    replay: DeviceReplay
    rng: jax.Array             # jax PRNG key
    step_count: jax.Array      # () int32 — drives the epsilon schedule


def engine_init(cfg: PolicyConfig, params: PolicyParams, opt: AdamState,
                num_nodes: int, *, seed: int = 0, step_count: int = 0,
                mesh=None) -> EngineState:
    """Fresh training carry.  With ``mesh`` (the cfg's 2-D device mesh)
    every buffer is placed in its mesh-resident layout from step 0 —
    replay tuple rows over ``data``, S masks over ``(data, graph)``,
    params/opt/rng replicated — so the buffers the first fused step
    donates alias its outputs instead of warning and copying."""
    replay = device_replay_init(cfg.replay_capacity, num_nodes)
    rng = jax.random.key(seed)
    sc = jnp.asarray(step_count, jnp.int32)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        replay = shard_replay(mesh, replay)
        rs = NamedSharding(mesh, PartitionSpec())
        place = lambda x: jax.device_put(x, rs)
        params = jax.tree.map(place, params)
        opt = jax.tree.map(place, opt)
        rng, sc = place(rng), place(sc)
    return EngineState(params=params, opt=opt, replay=replay, rng=rng,
                       step_count=sc)


def sync_to_agent(agent, es: EngineState) -> None:
    """Copy the carry's learned state back onto a host Agent (for eval and
    for resuming).  Copies go through the host: the next fused step donates
    the carry's buffers, and spatial runs leave arrays committed to the
    training mesh, which would clash with single-device eval jits."""
    pull = lambda x: jnp.asarray(np.asarray(x))
    agent.params = jax.tree.map(pull, es.params)
    agent.opt = jax.tree.map(pull, es.opt)
    agent.step_count = int(es.step_count)


def _check_csr_spatial(rep: GraphRep, sp: int) -> None:
    """CSR has no spatial (graph-axis) sharding path yet: its flat edge
    arrays are row-RAGGED, so an N/sp node split gives unequal per-device
    edge counts — unlike the dense row blocks / padded neighbor-list rows
    shard_map slices.  Fail fast with the supported alternatives instead of
    silently falling back (ISSUE 7)."""
    if rep.name == "csr" and sp > 1:
        raise ValueError(
            f"rep='csr' does not support spatial (graph-axis) sharding "
            f"sp={sp}: CSR rows are ragged, so node-partitioned shard_map "
            f"blocks would carry unequal edge counts — true for the "
            f"manual-collective path too (collectives='manual' exchanges "
            f"equal-size row tiles, which ragged CSR rows cannot supply), "
            f"so the flag does not change csr's status. Use "
            f"spatial=(dp, 1) for data parallelism with csr, or "
            f"rep='sparse'/'dense' for sp>1 graph partitioning.")


def get_train_step(cfg: PolicyConfig, *,
                   rep: Union[str, GraphRep, None] = None,
                   problem: str = "mvc", tau: Optional[int] = None,
                   target_mode: str = "fresh", explore: bool = True,
                   donate: bool = True):
    """Build (and cache) the fused jitted train step for a configuration.

    Returns ``step(es, state, source, graph_idx) -> (es', state', action,
    reward, done, loss)``.  ``source`` is the device-resident training
    dataset in ``rep``'s layout; ``graph_idx`` the (B,) episode graph ids.
    ``cfg.spatial`` selects the 2-D ``(data, graph)`` mesh (DESIGN.md
    §10; an int P back-compats to ``(1, P)``): acting, env transitions
    and replay run with the episode batch sharded over ``data``
    (bit-identical per-graph arithmetic), the GD loss/grad runs under
    shard_map on the (B/dp, N/sp, ·) tiled layout (minibatch must divide
    by dp, N by sp) with loss and gradients psum-ed over BOTH axes, and
    the replay ring buffer shards its tuple rows over ``data`` and its
    O(N) masks over ``(data, graph)``.

    ``cfg.collectives`` selects the mesh GD strategy (DESIGN.md §10):
    ``"manual"`` (the ``auto`` default on dp>1 ∧ sp>1) runs the
    manual-collective path — the replay ring is sampled by exchanging row
    tiles over ``data`` and topology is re-materialized per tile, so no
    loss operand is ever replicated; ``"gspmd"`` runs the GSPMD-partitioned
    reference path.

    ``donate=True`` donates the engine carry AND the episode state to the
    step (``donate_argnums``): replay ring, params, opt and state buffers
    are reused in place instead of double-buffered — callers must rebind
    both from the returns (every in-repo caller does).  ``False`` keeps
    the copying behaviour for before/after measurement
    (benchmarks/train_step_scaling.py).
    """
    rep = get_rep(rep if rep is not None else cfg.graph_rep)
    tau = cfg.grad_iters if tau is None else tau
    assert target_mode in ("fresh", "stored"), target_mode
    dp, _sp = normalize_spatial(cfg.spatial)
    if cfg.minibatch % dp:
        raise ValueError(f"minibatch {cfg.minibatch} not divisible by the "
                         f"data-axis size {dp} of mesh spec {cfg.spatial!r}")
    return _build_train_step(cfg, rep, problem, tau, target_mode, explore,
                             donate)


@functools.lru_cache(maxsize=64)
def _build_train_step(cfg: PolicyConfig, rep: GraphRep, problem: str,
                      tau: int, target_mode: str, explore: bool,
                      donate: bool = True):
    step_fn = env_lib.make(problem)
    residual = env_lib.residual_mode(problem)
    cand_fn = env_lib.candidate_rule(problem)
    num_layers, gamma = cfg.num_layers, cfg.gamma
    minibatch, lr = cfg.minibatch, cfg.learning_rate
    stored = target_mode == "stored"

    kernel, compute = cfg.kernel, cfg.compute
    dp, sp = normalize_spatial(cfg.spatial)
    manual_gd = None
    if (dp, sp) != (1, 1):
        _check_csr_spatial(rep, sp)
        mesh = make_mesh(dp, sp)
        if rep.name == "csr":
            if cfg.collectives == "manual":
                raise ValueError(
                    "collectives='manual' does not apply to rep='csr': "
                    "csr shards the batch only (sp == 1) and trains on "
                    "the plain data-parallel step, which never replicates "
                    "an operand — leave collectives='auto'")
            # data-parallel only (sp == 1 guaranteed above): the plain
            # minibatch step, its scores taken per graph over `data` — no
            # shard_map retiling of ragged edge rows.
            gd_step = functools.partial(
                train_minibatch_raw, rep=rep, num_layers=num_layers, lr=lr,
                kernel=kernel, compute=compute,
                score=per_graph_scorer(mesh, rep, num_layers=num_layers,
                                       kernel=kernel, compute=compute))
        else:
            coll = resolve_collectives(cfg.collectives, dp, sp)
            if coll == "manual" and cand_fn is not None:
                # candidate_fn envs (mds) override candidates on the FULL
                # assembled state — the tile-local remat cannot honor
                # that, so auto falls back to the reference path.
                if cfg.collectives == "manual":
                    raise ValueError(
                        f"collectives='manual' does not support envs with "
                        f"a candidate_fn override (problem={problem!r}): "
                        f"the override runs on the full assembled state; "
                        f"use collectives='gspmd' or 'auto'")
                coll = "gspmd"
            if coll == "manual":
                from .spatial import manual_train_minibatch_fn
                manual_gd = manual_train_minibatch_fn(
                    mesh, rep=rep, num_layers=num_layers, lr=lr,
                    gamma=gamma, minibatch=minibatch, residual=residual,
                    target_mode=target_mode, kernel=kernel,
                    compute=compute, jit=False)
                gd_step = None
            else:
                from .spatial import spatial_train_minibatch_fn
                gd_step = spatial_train_minibatch_fn(
                    mesh, num_layers=num_layers, lr=lr, jit=False,
                    kernel=kernel, compute=compute)
    else:
        mesh = None
        gd_step = functools.partial(train_minibatch_raw, rep=rep,
                                    num_layers=num_layers, lr=lr,
                                    kernel=kernel, compute=compute)
    score = per_graph_scorer(mesh, rep, num_layers=num_layers,
                             kernel=kernel, compute=compute)

    def _epsilon(step_count):
        frac = jnp.minimum(1.0, step_count.astype(jnp.float32)
                           / max(1, cfg.eps_decay_steps))
        return cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac

    # Donate the carry AND the episode state (DESIGN.md §10 donation
    # contract): replay ring, params, opt and state are rebound from the
    # returns by every caller, so steady-state steps mutate the resident
    # buffers in place instead of double-buffering them.
    @functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def train_step(es: EngineState, state, source, graph_idx):
        if mesh is not None:
            # Graph-level batch parallelism: the episode batch lives B/dp
            # per device (per-graph rows stay whole, so acting and the env
            # transition are bit-identical to the single-device path).
            state = constrain_batch(mesh, state)
            if manual_gd is not None:
                # manual-collective GD reads the dataset by resident node
                # rows — pin its layout to the shard_map in_specs.
                source = constrain_dataset(mesh, source)
        b = state.candidate.shape[0]
        rng, k_eps, k_pick, k_train = jax.random.split(es.rng, 4)

        # -- act (Alg. 1 lines 9-10) --------------------------------------
        scores = score(es.params, state)
        action = jnp.argmax(scores, axis=-1)
        if explore:
            logits = jnp.where(state.candidate > 0.5, 0.0, NEG_INF)
            pick = jax.random.categorical(k_pick, logits, axis=-1)
            roll = jax.random.uniform(k_eps, (b,)) < _epsilon(es.step_count)
            has_cand = state.candidate.sum(-1) > 0
            action = jnp.where(roll & has_cand, pick, action)

        # -- env transition -----------------------------------------------
        new_state, reward, done = step_fn(state, action)

        # -- remember (Alg. 5 lines 11-13) --------------------------------
        if stored:
            nxt = max_q_from_scores(score(es.params, new_state),
                                    new_state.candidate)
            target = reward + gamma * nxt * (1.0 - done.astype(jnp.float32))
        else:
            target = jnp.zeros_like(reward)
        replay = device_replay_push(es.replay, graph_idx, state.solution,
                                    action, target, reward,
                                    new_state.solution, done)
        if mesh is not None:
            # §5.2 generalized: tuple rows over `data`, S masks over
            # (data, graph) — per-device replay 8·R·(N/sp + 1)/dp bytes.
            replay = constrain_replay(mesh, replay)

        # -- τ GD iterations (Alg. 5 lines 15-23, §4.5.2) ------------------
        def do_train(carry):
            params, opt = carry

            def body(c, key):
                params, opt = c
                if manual_gd is not None:
                    # The manual path samples the ring itself (the same
                    # index stream) and exchanges row tiles over `data` —
                    # no replicated minibatch ever materializes.
                    params, opt, loss = manual_gd(params, opt, replay,
                                                  source, key)
                    return (params, opt), loss
                gi, sol, act, tgt, rew, sol2, dn = device_replay_sample(
                    replay, key, minibatch)
                if not stored:
                    st2 = rep.state_from_tuples(source, gi, sol2,
                                                residual=residual,
                                                candidate_fn=cand_fn)
                    nxt = max_q_from_scores(score(params, st2),
                                            st2.candidate)
                    tgt = rew + gamma * nxt * (1.0 - dn)
                st = rep.state_from_tuples(source, gi, sol,
                                           residual=residual,
                                           candidate_fn=cand_fn)
                params, opt, loss = gd_step(params, opt, st, act, tgt)
                return (params, opt), loss

            (params, opt), losses = lax.scan(
                body, (params, opt), jax.random.split(k_train, tau))
            return params, opt, losses[-1]

        def skip(carry):
            params, opt = carry
            return params, opt, jnp.float32(jnp.nan)

        warm = replay.size >= minibatch
        if tau > 0:
            params, opt, loss = lax.cond(warm, do_train, skip,
                                         (es.params, es.opt))
        else:
            params, opt, loss = skip((es.params, es.opt))

        # step_count drives the epsilon schedule; like the host loop's
        # Agent.train it only advances once the replay is warm.
        es = EngineState(params=params, opt=opt, replay=replay, rng=rng,
                         step_count=es.step_count + warm.astype(jnp.int32))
        return es, new_state, action, reward, done, loss

    return train_step


# ---------------------------------------------------------------------------
# Fused inference engine (paper Alg. 4 as ONE jitted while_loop).
# ---------------------------------------------------------------------------

def get_solve_step(*, rep: Union[str, GraphRep, None] = None,
                   problem: str = "mvc", num_layers: int = 2,
                   use_adaptive: bool = False, spatial: MeshSpec = 0,
                   kernel: str = "fused", compute: str = "f32",
                   max_d: int = 8, donate: bool = True):
    """Build (and cache) the fused device-resident solve for a configuration.

    Returns ``solve_fn(params, state, max_evals) -> (final_state, evals,
    committed)`` — the ENTIRE Alg. 4 loop (score → top-d commit → done
    check) as one jitted ``lax.while_loop`` with no per-eval host traffic;
    the caller's single result fetch is the solve's only host↔device sync.
    ``spatial`` selects the 2-D ``(data, graph)`` mesh (an int P
    back-compats to ``(1, P)``, DESIGN.md §10): the while_loop runs with
    the batch sharded over ``data`` — B/dp graphs per device, the done
    check reduced over the mesh — and each policy evaluation partitioned
    sp-way under shard_map (same per-eval collectives as the 1-D spatial
    path, DESIGN.md §3), with the top-d commit in the paper's Fig. 4
    lockstep.  A dense adjacency keeps its rows split over ``graph`` in
    the carry for the whole solve (``mesh.DENSE_SOLVE_SPECS``): each
    device scores, selects and commits on its own rows
    (``spatial.dense_solve_eval_fn``) and none holds the whole graph.  The
    sparse rep's neighbor lists are retiled over ``graph`` per
    evaluation.  ``max_d`` widens the adaptive top-d cap beyond the paper's
    8 for paper-scale solves (see ``inference.solve``).  ``donate=True``
    donates the solve state into the while_loop (callers never reuse it
    — every solve builds the state fresh); ``False`` keeps the copying
    behaviour for before/after measurement.
    """
    rep = get_rep(rep)
    return _build_solve_step(rep, problem, num_layers, bool(use_adaptive),
                             normalize_spatial(spatial), kernel, compute,
                             int(max_d), bool(donate))


@functools.lru_cache(maxsize=64)
def _build_solve_step(rep: GraphRep, problem: str, num_layers: int,
                      use_adaptive: bool, spatial: tuple, kernel: str,
                      compute: str, max_d: int, donate: bool = True):
    dp, sp = spatial
    mesh = evaluate = None
    if (dp, sp) != (1, 1):
        _check_csr_spatial(rep, sp)
        mesh = make_mesh(dp, sp)
        if rep.name == "csr":
            # data-parallel only (sp == 1 guaranteed above): plain scoring
            # with the batch over `data`.
            score_fn = per_graph_scorer(mesh, rep, num_layers=num_layers,
                                        kernel=kernel, compute=compute)
        elif rep.name == "dense":
            # the adjacency rows stay split over `graph` for the whole
            # solve: score, select and commit run on the resident rows
            from .spatial import dense_solve_eval_fn
            evaluate = dense_solve_eval_fn(
                mesh, problem=problem, num_layers=num_layers,
                use_adaptive=use_adaptive, max_d=max_d, kernel=kernel,
                compute=compute)
        else:
            from .spatial import spatial_solve_scores_fn
            score_fn = spatial_solve_scores_fn(
                mesh, num_layers=num_layers, rep=rep,
                residual=env_lib.sparse_residual_flag(problem),
                kernel=kernel, compute=compute)
    else:
        def score_fn(params, state):
            return rep.scores(params, state, num_layers=num_layers,
                              kernel=kernel, compute=compute)

    if evaluate is None:
        def evaluate(params, state):
            # env-polymorphic select → prune → commit, shared verbatim
            # with the host-loop step (bit-identical engines)
            return apply_selection(state, score_fn(params, state),
                                   state.candidate, use_adaptive, problem,
                                   max_d)

    # Donate the init state into the while_loop: every caller builds the
    # state fresh per solve (inference.solve, serving._dispatch) and never
    # touches it afterwards, so its buffers carry the loop in place.
    @functools.partial(jax.jit, donate_argnums=(1,) if donate else ())
    def solve_fn(params, state, max_evals):
        if mesh is not None:
            # the carry's layout (mesh.solve_state_specs): B/dp graphs per
            # device, and a dense adjacency's rows over `graph`
            state = constrain_solve_state(mesh, state)
        b = state.candidate.shape[0]

        def cond(carry):
            _state, evals, _committed, done = carry
            # `done` is data-sharded with the batch: the all() is the
            # done-check reduction over the mesh.
            return jnp.logical_and(~done.all(), evals < max_evals)

        def body(carry):
            state, evals, committed, _done = carry
            new_state, done, ncommit = evaluate(params, state)
            return (new_state, evals + 1, committed + ncommit, done)

        init = (state, jnp.int32(0), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool))
        state, evals, committed, _done = lax.while_loop(cond, body, init)
        # the FULL final state comes back (callers read .solution) so
        # every donated state buffer — topology included — aliases an
        # output and the loop runs in place
        return state, evals, committed

    return solve_fn
