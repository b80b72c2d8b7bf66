"""The RL agent's combined policy model: EM (structure2vec) followed by Q
(action evaluation) — paper §4.2, "the two models are connected into one
combined model" so both are trained jointly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .s2v import (S2VParams, init_s2v, embed_local, check_kernel,
                  compute_dtype)
from .qmodel import QParams, init_q, scores_local


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PolicyParams:
    em: S2VParams
    q: QParams

    @property
    def dim(self) -> int:
        return self.em.dim


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """Paper §6.1 hyper-parameter settings."""
    embed_dim: int = 32          # K
    num_layers: int = 2          # L
    gamma: float = 0.9           # discount
    learning_rate: float = 1e-5
    replay_capacity: int = 50_000
    eps_start: float = 0.9
    eps_end: float = 0.1
    eps_decay_steps: int = 500
    minibatch: int = 64          # B tuples per GD iteration
    grad_iters: int = 1          # τ (paper §4.5.2)
    graph_rep: str = "dense"     # GraphRep backend: "dense" | "sparse" | "csr"
    # Training-engine selection (DESIGN.md §8), config-driven like graph_rep:
    engine: str = "device"       # "device" (fused jitted step) | "host"
    # 2-D (data, graph) device-mesh spec (DESIGN.md §10): a (dp, sp) tuple
    # shards batches dp ways over `data` and node rows sp ways over
    # `graph`.  Back-compat: an int P means the legacy 1-D node sharding
    # (1, P); 0 → single device, no mesh.
    spatial: Union[int, Tuple[int, int]] = 0
    # S2V layer lowering (DESIGN.md §12): "fused" = one launch per layer
    # (Pallas super-kernel on TPU, single XLA composition elsewhere) with
    # layer-0 elision; "xla" = the reference per-op chain.
    kernel: str = "fused"
    # Matmul operand precision: "f32" | "bf16" (f32 accumulation, f32
    # residual/ReLU/Q-model, f32 master params).
    compute: str = "f32"
    # Cross-shard communication strategy of the mesh GD step (DESIGN.md
    # §10): "manual" = hand-written lax collectives over per-device tiles
    # (no operand ever replicated); "gspmd" = the GSPMD-partitioned
    # reference path; "auto" = manual on dp>1 ∧ sp>1 meshes, gspmd
    # otherwise.
    collectives: str = "auto"

    def __post_init__(self):
        check_kernel(self.kernel)
        compute_dtype(self.compute)   # validates the mode name
        from .mesh import check_collectives
        check_collectives(self.collectives)


def init_policy(key: jax.Array, cfg: PolicyConfig) -> PolicyParams:
    k1, k2 = jax.random.split(key)
    return PolicyParams(em=init_s2v(k1, cfg.embed_dim),
                        q=init_q(k2, cfg.embed_dim))


def num_params(cfg: PolicyConfig) -> int:
    """4K² + 4K — the gradient all-reduce payload (paper §5.1(3))."""
    k = cfg.embed_dim
    return 4 * k * k + 4 * k


def policy_scores(
    params: PolicyParams,
    adj_local: jax.Array,      # (B, Nl, N)
    sol_local: jax.Array,      # (B, Nl)
    cand_local: jax.Array,     # (B, Nl)
    *,
    num_layers: int,
    axis: Optional[str] = None,
    masked: bool = True,
    kernel: str = "fused",
    compute: str = "f32",
) -> jax.Array:
    """Q(EM(Aᶦ, Sᶦ), Cᶦ): (B, Nl) masked scores of local candidates.

    The embedding runs under the named scope ``s2v.embed`` and the Q head
    under ``q.head`` (as in every score path), so compiled operations and
    their device-trace events carry the layer in their ``op_name``."""
    with jax.named_scope("s2v.embed"):
        emb = embed_local(params.em, adj_local, sol_local,
                          num_layers=num_layers, axis=axis, kernel=kernel,
                          compute=compute)
    with jax.named_scope("q.head"):
        return scores_local(params.q, emb, cand_local, axis=axis,
                            masked=masked)
