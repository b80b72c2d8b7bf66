"""Action-evaluation model (paper Eq. 2, Alg. 3).

Scores every local candidate node from the local embeddings.  One all-reduce
of a (B, K) buffer (paper Alg. 3 line 5) when running spatially partitioned.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .s2v import f32_matmuls

NEG_INF = -1e9


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QParams:
    theta5: jax.Array  # (K, K)
    theta6: jax.Array  # (K, K)
    theta7: jax.Array  # (2K,)

    @property
    def dim(self) -> int:
        return self.theta5.shape[0]


def init_q(key: jax.Array, k: int, scale: float = 0.1) -> QParams:
    k5, k6, k7 = jax.random.split(key, 3)
    s = scale / jnp.sqrt(k)
    return QParams(
        theta5=jax.random.normal(k5, (k, k)) * s,
        theta6=jax.random.normal(k6, (k, k)) * s,
        theta7=jax.random.normal(k7, (2 * k,)) * s,
    )


@f32_matmuls
def scores_local(
    params: QParams,
    embed_local: jax.Array,     # (B, K, Nl)
    cand_local: jax.Array,      # (B, Nl) candidate mask
    *,
    axis: Optional[str] = None,
    masked: bool = True,
) -> jax.Array:
    """Alg. 3: returns (B, Nl) scores; non-candidates get NEG_INF if masked."""
    # Lines 4-5: global graph embedding sum (all-reduce of B×K)
    sum_embed = embed_local.sum(-1)                          # (B, K)
    if axis is not None:
        with jax.named_scope("mesh.collective"):
            sum_embed = lax.psum(sum_embed, axis)
    # Line 6: w1 = θ5 @ Σ embed
    w1 = jnp.einsum("kj,bj->bk", params.theta5, sum_embed)   # (B, K)
    # Lines 8-9: candidate extraction (sparse diag) then θ6 projection
    cand_embed = embed_local * cand_local[:, None, :]        # (B, K, Nl)
    w2 = jnp.einsum("kj,bjn->bkn", params.theta6, cand_embed)
    # Line 10: concat + relu  → (B, 2K, Nl)
    nl = embed_local.shape[-1]
    w1b = jnp.broadcast_to(w1[:, :, None], w2.shape)
    w3 = jax.nn.relu(jnp.concatenate([w1b, w2], axis=1))
    # Line 11: scores = θ7ᵀ @ w3
    scores = jnp.einsum("c,bcn->bn", params.theta7, w3)      # (B, Nl)
    if masked:
        scores = jnp.where(cand_local > 0.5, scores, NEG_INF)
    return scores
