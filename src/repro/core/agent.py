"""Graph Learning Agent (paper Fig. 1, Alg. 1): epsilon-greedy deep-Q agent
over the combined structure2vec + action-evaluation policy.

Training follows Alg. 5: targets are computed at experience-insertion time
(``target = reward + γ·max_v Q(s', v)``, line 12), tuples are stored
compressed, and each env step runs τ gradient-descent iterations (§4.5.2)
over minibatches re-materialized by Tuples2Graphs.

The agent is representation-polymorphic (DESIGN.md §1): acting, target
bootstrapping and minibatch training dispatch through the GraphRep backend
matching the state/dataset layout, so the same replay buffer of compressed
``(graph id, S, action, target)`` tuples drives both the dense and the
sparse path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .graphs import CsrGraphBatch, GraphState, SparseGraphBatch
from .graphrep import CSR, DENSE, SPARSE, GraphRep, get_rep, rep_for_state
from .mesh import is_multi
from .policy import PolicyConfig, PolicyParams, init_policy, policy_scores
from .qmodel import NEG_INF
from .replay import ReplayBuffer, tuples_to_graphs
from ..optim import AdamState, adam_init, adam_update


def candidate_mask(adj: jax.Array, solution: jax.Array) -> jax.Array:
    deg = adj.sum(-1)
    return ((deg > 0) & (solution < 0.5)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("rep", "num_layers", "kernel",
                                             "compute"))
def greedy_action_state(params: PolicyParams, state, *, rep: GraphRep,
                        num_layers: int, kernel: str = "fused",
                        compute: str = "f32"):
    """argmax_v Q(s, v) over candidates (exploit path of Alg. 1 line 10)."""
    s = rep.scores(params, state, num_layers=num_layers, kernel=kernel,
                   compute=compute)
    return jnp.argmax(s, axis=-1), s


def max_q_from_scores(scores: jax.Array, candidate: jax.Array):
    """max_v Q(s', v) from masked scores, with the no-candidate
    convention (0)."""
    return jnp.where(candidate.sum(-1) > 0, scores.max(-1), 0.0)


def max_q_raw(params: PolicyParams, state, *, rep: GraphRep,
              num_layers: int, kernel: str = "fused", compute: str = "f32"):
    """max_v Q(s', v) of a state — un-jitted so callers can trace it
    inline."""
    s = rep.scores(params, state, num_layers=num_layers, kernel=kernel,
                   compute=compute)
    return max_q_from_scores(s, state.candidate)


max_q_state = functools.partial(
    jax.jit, static_argnames=("rep", "num_layers", "kernel",
                              "compute"))(max_q_raw)


@functools.partial(jax.jit, static_argnames=("num_layers",))
def greedy_action(params: PolicyParams, adj, sol, cand, *, num_layers: int):
    """Dense-array convenience wrapper (kept for existing callers)."""
    s = policy_scores(params, adj, sol, cand, num_layers=num_layers)
    return jnp.argmax(s, axis=-1), s


@functools.partial(jax.jit, static_argnames=("num_layers",))
def max_q(params: PolicyParams, adj, sol, cand, *, num_layers: int):
    s = policy_scores(params, adj, sol, cand, num_layers=num_layers)
    has_cand = cand.sum(-1) > 0
    return jnp.where(has_cand, s.max(-1), 0.0)


def train_minibatch_raw(params: PolicyParams, opt: AdamState, state,
                        action, target, *, rep: GraphRep, num_layers: int,
                        lr: float, kernel: str = "fused",
                        compute: str = "f32", score=None):
    """One GD iteration on a re-materialized minibatch (Alg. 5 lines 19-23).
    Un-jitted building block shared by the host path (jitted below), the
    fused train step's scan body and the spatial shard_map path.
    ``score(params, state, masked)`` replaces ``rep.scores`` where the
    scores must be taken per device (``engine.per_graph_scorer``)."""
    def loss_fn(p):
        if score is not None:
            s = score(p, state, masked=False)
        else:
            s = rep.scores(p, state, num_layers=num_layers, masked=False,
                           kernel=kernel, compute=compute)
        qsa = jnp.take_along_axis(s, action[:, None], axis=-1)[:, 0]
        return jnp.mean(jnp.square(qsa - target))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt = adam_update(params, grads, opt, lr=lr)
    return params, opt, loss


_train_minibatch = functools.partial(
    jax.jit, static_argnames=("rep", "num_layers", "kernel", "compute"),
    donate_argnums=(0, 1))(train_minibatch_raw)


@dataclasses.dataclass
class Agent:
    """Host-side agent driver (episodes/replay are host logic, everything
    numerical is jitted and device-resident)."""
    cfg: PolicyConfig
    num_nodes: int
    params: PolicyParams = None
    opt: AdamState = None
    replay: ReplayBuffer = None
    step_count: int = 0
    target_mode: str = "fresh"          # "fresh" | "stored" (paper Alg. 5)

    def __post_init__(self):
        if self.params is None:
            self.params = init_policy(jax.random.key(0), self.cfg)
        if self.opt is None:
            self.opt = adam_init(self.params)
        if self.replay is None:
            self.replay = ReplayBuffer(self.cfg.replay_capacity, self.num_nodes)
        self._rng = np.random.default_rng(0)
        self._spatial_fn = None

    def _spatial_minibatch(self):
        """Cached mesh-parallel GD step (paper Alg. 5 lockstep, 2-D mesh;
        DESIGN.md §8/§10) on ``cfg.spatial``'s ``(dp, sp)`` device mesh;
        dispatches on state type."""
        if self._spatial_fn is None:
            from .mesh import mesh_from_spec
            from .spatial import spatial_train_minibatch_fn
            self._spatial_fn = spatial_train_minibatch_fn(
                mesh_from_spec(self.cfg.spatial),
                num_layers=self.cfg.num_layers,
                lr=self.cfg.learning_rate,
                kernel=self.cfg.kernel, compute=self.cfg.compute)
        return self._spatial_fn

    # -- acting ------------------------------------------------------------
    def epsilon(self) -> float:
        c = self.cfg
        frac = min(1.0, self.step_count / max(1, c.eps_decay_steps))
        return c.eps_start + (c.eps_end - c.eps_start) * frac

    def act(self, state, explore: bool = True) -> np.ndarray:
        """Batched epsilon-greedy action (Alg. 1 lines 9-10); works on both
        representations via state-type dispatch."""
        b, n = state.candidate.shape
        greedy, _ = greedy_action_state(self.params, state,
                                        rep=rep_for_state(state),
                                        num_layers=self.cfg.num_layers,
                                        kernel=self.cfg.kernel,
                                        compute=self.cfg.compute)
        greedy = np.asarray(greedy)
        if not explore:
            return greedy
        eps = self.epsilon()
        cand = np.asarray(state.candidate) > 0.5
        explore_row = (self._rng.random(b) < eps) & cand.any(-1)
        # Batched masked random choice: the argmax of iid uniforms restricted
        # to candidate slots is a uniform draw from each row's candidate set.
        u = self._rng.random((b, n)) * cand
        return np.where(explore_row, np.argmax(u, axis=-1), greedy)

    # -- remembering ---------------------------------------------------------
    def remember(self, graph_idx, prev_state, action,
                 reward, next_state, done) -> None:
        """Store compressed tuples.

        ``target_mode="stored"`` computes the TD target now (paper Alg. 5
        line 12, verbatim); ``"fresh"`` (default) stores (r, S', done) —
        still O(N) per tuple — and bootstraps with CURRENT params at
        training time, which is markedly more stable at practical learning
        rates (EXPERIMENTS.md §Paper-claims notes the deviation).
        """
        if self.target_mode == "stored":
            nxt = max_q_state(self.params, next_state,
                              rep=rep_for_state(next_state),
                              num_layers=self.cfg.num_layers,
                              kernel=self.cfg.kernel,
                              compute=self.cfg.compute)
            target = np.asarray(reward) + self.cfg.gamma * np.asarray(nxt) * (
                1.0 - np.asarray(done, np.float32))
        else:
            target = np.zeros_like(np.asarray(reward))
        self.replay.push_batch(graph_idx, np.asarray(prev_state.solution),
                               action, target, reward=np.asarray(reward),
                               next_solution=np.asarray(next_state.solution),
                               done=np.asarray(done))

    # -- training -----------------------------------------------------------
    def train(self, source, tau: Optional[int] = None,
              residual=True, candidate_fn=None) -> float:
        """τ gradient-descent iterations on sampled minibatches (§4.5.2).

        ``source`` is the training-graph dataset in any representation:
        a (G, N, N) dense adjacency stack, a ``SparseGraphBatch`` of
        (G, N, D) neighbor lists (from ``SparseRep.prepare_dataset``), or
        a ``CsrGraphBatch`` of flat edge arrays — e.g. sampled training
        subgraphs from ``sampling.NeighborSampler.training_batch``.
        ``residual`` carries the env's topology mode and ``candidate_fn``
        its candidate derivation (see ``env.register``) so replay states
        are re-materialized on the graph the policy acts on.
        """
        rep = (CSR if isinstance(source, CsrGraphBatch)
               else SPARSE if isinstance(source, SparseGraphBatch) else DENSE)
        tau = self.cfg.grad_iters if tau is None else tau
        if self.replay.size < self.cfg.minibatch:
            return float("nan")
        loss = float("nan")
        for _ in range(tau):
            gi, sol, act, tgt, rew, sol2, done = self.replay.sample(
                self.cfg.minibatch, self._rng)
            if self.target_mode == "fresh":
                st2 = rep.state_from_tuples(source, gi, sol2,
                                            residual=residual,
                                            candidate_fn=candidate_fn)
                nxt = max_q_state(self.params, st2, rep=rep,
                                  num_layers=self.cfg.num_layers,
                                  kernel=self.cfg.kernel,
                                  compute=self.cfg.compute)
                tgt = rew + self.cfg.gamma * np.asarray(nxt) * (1.0 - done)
            st = rep.state_from_tuples(source, gi, sol, residual=residual,
                                       candidate_fn=candidate_fn)
            if is_multi(self.cfg.spatial):
                self.params, self.opt, l = self._spatial_minibatch()(
                    self.params, self.opt, st,
                    jnp.asarray(act), jnp.asarray(tgt))
            else:
                self.params, self.opt, l = _train_minibatch(
                    self.params, self.opt, st,
                    jnp.asarray(act), jnp.asarray(tgt),
                    rep=rep, num_layers=self.cfg.num_layers,
                    lr=self.cfg.learning_rate,
                    kernel=self.cfg.kernel, compute=self.cfg.compute)
            loss = float(l)
        self.step_count += 1
        return loss
