"""Sparse (gather-based) structure2vec path — the paper's "distributed
sparse graph storage" (§4.1, §5.2) made TPU-native.

The dense path stores the residual adjacency (B, N, N) and *rewrites* it
every step.  This path stores the ORIGINAL topology once as a padded
neighbor list (B, N, D) plus the dynamic partial-solution mask S: a residual
edge (u,v) exists iff the original edge exists and neither endpoint is in S,
so message passing becomes a gather over static indices with mask factors —
memory O(N·maxdeg) instead of O(N²), and no per-step adjacency rewrite.

This is the TPU adaptation of the paper's COO/cuSPARSE storage (DESIGN.md
§2): gathers over a padded index tensor instead of sparse matmuls.

``embed_sparse_local`` is the distributed form (paper Alg. 2 on sparse
storage): each device holds the (B, N/P, D) neighbor-list rows of its
resident nodes; one all-gather of the (B, K, N) embedding buffer per layer
replaces the dense path's all-reduce.

``kernel="fused"`` (default) runs each layer as ONE fused launch —
gather/aggregate → θ4-matmul → residual add → ReLU — via the Pallas
super-kernel ``repro.kernels.s2v_fused.fused_s2v_layer_sparse`` on TPU
(where the size rule ``repro.core.s2v.s2v_kernel_fits`` admits it) and
the equivalent single XLA composition elsewhere, and elides layer 0
entirely (zero-initialized embeddings make the first aggregation exactly
zero, so layer 1 is relu(embed1+embed2) — bit-identical, and one
all-gather fewer per eval when sharded).  ``kernel="xla"`` is the
reference per-op chain; ``gather_impl`` plugs a custom aggregation into it
(the Pallas gather kernel from ``repro.kernels.s2v_gather`` on TPU).
``compute="bf16"`` casts matmul operands to bf16 with f32 accumulation
(DESIGN.md §12).

The solve driver lives in ``repro.core.inference`` — use
``solve(..., rep="sparse")``; representation dispatch is handled by
``repro.core.graphrep``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .graphs import (SparseGraphBatch, SparseGraphState,
                     closed_neighborhood_keep, residual_edge_mask,
                     sparse_batch_from_dense)
from .policy import PolicyParams
from .qmodel import scores_local, NEG_INF
from .s2v import (check_kernel, compute_dtype, f32_matmuls,
                  s2v_layer_impl)

__all__ = ["SparseGraphBatch", "sparse_batch_from_dense", "embed_sparse",
           "embed_sparse_local", "residual_edge_factors",
           "closed_edge_factors", "closed_keep_local", "edge_factors",
           "sparse_local_scores", "sparse_policy_scores",
           "sparse_state_bytes"]


def residual_edge_factors(nbr_local: jax.Array, valid_local: jax.Array,
                          sol_local: jax.Array, *,
                          axis: Optional[str] = None) -> jax.Array:
    """(B, Nl, D) residual-edge factors: ``valid ∧ keep[u] ∧ keep[v]`` on
    DISTRIBUTED sparse storage — the one shared construction behind the
    spatial scores, spatial train-grad, and fused-solve paths.

    With ``axis`` naming the node-sharding mesh axis, the (B, Nl) local
    solution slice is all-gathered first (4·N·B bytes — the paper §5.1
    C/S broadcast) so the ``keep`` factors of REMOTE neighbor endpoints
    are visible to the local gather; the gathered mask is padded with a
    sentinel column for the padded neighbor slots.  ``axis=None`` is the
    single-device case (Nl == N), delegating to
    :func:`repro.core.graphs.residual_edge_mask`.
    """
    if axis is None:
        return residual_edge_mask(nbr_local, valid_local, sol_local)
    keep_local = 1.0 - sol_local
    keep_full = lax.all_gather(keep_local, axis, axis=1, tiled=True)
    keep_pad = jnp.pad(keep_full, ((0, 0), (0, 1)))          # sentinel slot
    keep_nbr = jax.vmap(lambda kb, nb: kb[nb])(keep_pad, nbr_local)
    return valid_local.astype(jnp.float32) * keep_nbr * keep_local[:, :, None]


def closed_keep_local(nbr_local: jax.Array, valid_local: jax.Array,
                      sol_local: jax.Array, *,
                      axis: Optional[str] = None) -> jax.Array:
    """(B, Nl) CLOSED-neighborhood keep factors on distributed storage: a
    resident node survives iff neither in S nor adjacent to it.  With
    ``axis`` named, the S slice is all-gathered over ``graph`` so each
    device can test adjacency against REMOTE solution nodes; ``axis=None``
    delegates to the single-device :func:`closed_neighborhood_keep`.
    Shared by the spatial scorers, the spatial train-grad path and the
    manual-collective GD remat."""
    if axis is None:
        return closed_neighborhood_keep(nbr_local, valid_local, sol_local)
    sol_full = lax.all_gather(sol_local, axis, axis=1, tiled=True)
    sol_pad = jnp.pad(sol_full, ((0, 0), (0, 1)))            # sentinel slot
    s_nbr = jax.vmap(lambda sb, nb: sb[nb])(sol_pad, nbr_local)
    any_nbr = (valid_local.astype(jnp.float32) * s_nbr).max(-1)
    return (1.0 - sol_local) * (1.0 - any_nbr)


def closed_edge_factors(nbr_local: jax.Array, valid_local: jax.Array,
                        sol_local: jax.Array, *,
                        axis: Optional[str] = None) -> jax.Array:
    """(B, Nl, D) CLOSED-neighborhood residual-edge factors (MIS): an edge
    survives iff neither endpoint is in S nor adjacent to S.

    Distributed (``axis`` named): :func:`closed_keep_local` all-gathers the
    S slice once so each device can mark its resident nodes adjacent to S,
    then the resulting per-node ``keep`` factors are all-gathered (a second
    (B, N) broadcast over ``graph``) so the local gather sees REMOTE
    endpoints' keeps.  ``axis=None`` is the single-device case (Nl == N)."""
    val = valid_local.astype(jnp.float32)
    keep_local = closed_keep_local(nbr_local, valid_local, sol_local,
                                   axis=axis)
    if axis is None:
        keep_full = keep_local
    else:
        keep_full = lax.all_gather(keep_local, axis, axis=1, tiled=True)
    keep_pad = jnp.pad(keep_full, ((0, 0), (0, 1)))
    keep_nbr = jax.vmap(lambda kb, nb: kb[nb])(keep_pad, nbr_local)
    return val * keep_nbr * keep_local[:, :, None]


def edge_factors(nbr_local: jax.Array, valid_local: jax.Array,
                 sol_local: jax.Array, residual, *,
                 axis: Optional[str] = None) -> jax.Array:
    """Edge-factor dispatch on the env's residual mode (``env.register``):
    ``True``/``"solution"`` → S's edges removed; ``"closed"`` → S's and
    its neighbors' edges removed (MIS); ``False``/``"none"`` → the
    original topology (MaxCut/MDS)."""
    if residual is False or residual == "none":
        return valid_local.astype(jnp.float32)
    if residual == "closed":
        return closed_edge_factors(nbr_local, valid_local, sol_local,
                                   axis=axis)
    return residual_edge_factors(nbr_local, valid_local, sol_local,
                                 axis=axis)


def _gather_neighbors(x: jax.Array, nbrs: jax.Array) -> jax.Array:
    """x (B, K, N+1) [zero-padded], nbrs (B, Nl, D) → (B, K, Nl, D)."""
    return jax.vmap(lambda xb, nb: xb[:, nb])(x, nbrs)


@f32_matmuls
def _gather_aggregate(xp: jax.Array, nbrs: jax.Array,
                      edge: jax.Array) -> jax.Array:
    """Reference aggregation: Σ_d xp[b,k,nbrs[b,i,d]]·edge[b,i,d] → (B,K,Nl).
    The Pallas kernel (``repro.kernels.s2v_gather``) implements the same
    contract tiled through VMEM."""
    gathered = _gather_neighbors(xp, nbrs)                  # (B, K, Nl, D)
    return jnp.einsum("bknd,bnd->bkn", gathered, edge)


def _default_gather_impl(k: int, max_degree: int) -> Optional[Callable]:
    """Aggregation hot loop of the reference "xla" chain: the Pallas gather
    kernel on TPU where the size rule admits it (VMEM-tiled, avoids
    materializing the (B, K, N, D) gather transient in HBM); pure-jnp
    gather elsewhere, where XLA's fused gather beats the interpret-mode
    kernel."""
    if s2v_layer_impl("sparse", k=k, max_degree=max_degree,
                      aggregate_only=True) == "pallas":
        from ..kernels.ops import sparse_mp_aggregate
        return sparse_mp_aggregate
    return None


@f32_matmuls
def _sparse_layer_jnp(theta4, x_full, nbr_local, edge_local, base, cd):
    """One fused sparse layer as a single XLA composition: gather/aggregate
    with cd-cast operands and f32 accumulation, θ4-matmul, residual + ReLU.
    x_full (B, K, N) has NO sentinel column (padded ids select the zero
    column appended here)."""
    xp = jnp.pad(x_full, ((0, 0), (0, 0), (0, 1))).astype(cd)
    gathered = _gather_neighbors(xp, nbr_local)             # (B, K, Nl, D)
    nbr = jnp.einsum("bknd,bnd->bkn", gathered, edge_local.astype(cd),
                     preferred_element_type=jnp.float32)
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(cd), nbr.astype(cd),
                    preferred_element_type=jnp.float32)
    return jax.nn.relu(base + e3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sparse_layer_hw(theta4, x_full, nbr_local, edge_local, base, cd):
    from ..kernels.ops import fused_s2v_layer_sparse
    return fused_s2v_layer_sparse(theta4, x_full, nbr_local, edge_local,
                                  base, compute_dtype=cd)


def _sparse_layer_hw_fwd(theta4, x_full, nbr_local, edge_local, base, cd):
    return _sparse_layer_hw(theta4, x_full, nbr_local, edge_local, base,
                            cd), (theta4, x_full, nbr_local, edge_local, base)


def _sparse_layer_hw_bwd(cd, res, g):
    _, vjp = jax.vjp(
        lambda t4, x, nb, ed, b: _sparse_layer_jnp(t4, x, nb, ed, b, cd),
        *res)
    return vjp(g)


_sparse_layer_hw.defvjp(_sparse_layer_hw_fwd, _sparse_layer_hw_bwd)


def _sparse_layer_fused(theta4, x_full, nbr_local, edge_local, base, cd):
    """Dispatch for one fused sparse layer by the size rule
    (:func:`repro.core.s2v.s2v_layer_impl`): the Pallas super-kernel on
    TPU, the jnp composition elsewhere (same policy as the gather)."""
    if s2v_layer_impl("sparse", k=x_full.shape[1],
                      max_degree=nbr_local.shape[2],
                      compute_dtype=cd) == "pallas":
        return _sparse_layer_hw(theta4, x_full, nbr_local, edge_local,
                                base, cd)
    return _sparse_layer_jnp(theta4, x_full, nbr_local, edge_local, base, cd)


@f32_matmuls
def embed_sparse_local(params, nbr_local: jax.Array, edge_local: jax.Array,
                       sol_local: jax.Array, *, num_layers: int,
                       axis: Optional[str] = None,
                       kernel: str = "fused", compute: str = "f32",
                       gather_impl: Optional[Callable] = None) -> jax.Array:
    """structure2vec over the residual graph implied by (topology, S),
    computed for the N/P resident nodes of this device (Alg. 2 on sparse
    storage).

    nbr_local (B, Nl, D) int32 GLOBAL neighbor ids; edge_local (B, Nl, D)
    residual-edge factors; sol_local (B, Nl).  With ``axis`` naming a
    shard_map mesh axis, each layer all-gathers the (B, K, N) embedding
    buffer so local gathers can reach remote-resident neighbors; axis=None
    is the single-device path (Nl == N).  ``kernel``/``compute`` select the
    fused super-kernel path and operand precision (see module docstring);
    ``gather_impl`` only applies to the reference ``"xla"`` chain.
    Returns (B, K, Nl)."""
    check_kernel(kernel)
    cd = compute_dtype(compute)
    b, nl, d = nbr_local.shape
    k = params.theta1.shape[0]
    agg = (gather_impl or _default_gather_impl(k, d)
           or _gather_aggregate)

    deg = edge_local.sum(-1)                                # residual degree
    embed1 = params.theta1[None, :, None] * sol_local[:, None, :]
    w = jax.nn.relu(params.theta2[None, :, None] * deg[:, None, :])
    embed2 = jnp.einsum("kj,bjn->bkn", params.theta3, w)
    base = embed1 + embed2                                  # f32 residual

    embed = jnp.zeros((b, k, nl), jnp.float32)
    for layer in range(num_layers):
        if kernel == "fused":
            if layer == 0:
                # embed⁰ = 0 ⇒ the first aggregation (and its all-gather)
                # is exactly zero ⇒ layer 1 is relu(base), bit-identical.
                embed = jax.nn.relu(base)
                continue
            if axis is not None:
                full = lax.all_gather(embed, axis, axis=2, tiled=True)
            else:
                full = embed                                 # Nl == N
            embed = _sparse_layer_fused(params.theta4, full, nbr_local,
                                        edge_local, base, cd)
            continue
        # Reference "xla" per-op chain (semantics of record).
        if axis is not None:
            # distributed sparse storage: gather the full embedding buffer
            # (the sparse analogue of the dense path's MPI_All_reduce)
            full = lax.all_gather(embed, axis, axis=2, tiled=True)
        else:
            full = embed                                     # Nl == N
        xp = jnp.pad(full, ((0, 0), (0, 0), (0, 1)))         # sentinel col
        nbr = agg(xp, nbr_local, edge_local)                 # (B, K, Nl)
        embed3 = jnp.einsum("kj,bjn->bkn", params.theta4, nbr)
        embed = jax.nn.relu(base + embed3)
    return embed


def embed_sparse(params, g, sol: jax.Array, *, num_layers: int,
                 residual=True, kernel: str = "fused", compute: str = "f32",
                 gather_impl: Optional[Callable] = None) -> jax.Array:
    """Single-device convenience wrapper: derives the edge factors for the
    env's ``residual`` mode from (topology, S) and embeds all N nodes.
    ``g`` is anything carrying ``neighbors``/``valid`` — a
    SparseGraphBatch or SparseGraphState.  ``residual=False`` embeds the
    original topology (MaxCut/MDS — selecting a node deletes no edges);
    ``"closed"`` drops S and its neighbors (MIS)."""
    edge = edge_factors(g.neighbors, g.valid, sol, residual, axis=None)
    return embed_sparse_local(params, g.neighbors, edge, sol,
                              num_layers=num_layers, axis=None,
                              kernel=kernel, compute=compute,
                              gather_impl=gather_impl)


def sparse_local_scores(params: PolicyParams, nbr_local: jax.Array,
                        valid_local: jax.Array, sol_local: jax.Array,
                        cand_local: jax.Array, *, num_layers: int,
                        residual=True, axis: Optional[str] = None,
                        masked: bool = True, kernel: str = "fused",
                        compute: str = "f32",
                        gather_impl: Optional[Callable] = None) -> jax.Array:
    """Q(EM(topology, S), C): (B, Nl) scores of the resident nodes, the
    edge factors and embedding under the named scope ``s2v.embed`` and the
    Q head under ``q.head``.  ``axis`` as in :func:`embed_sparse_local`."""
    with jax.named_scope("s2v.embed"):
        edge = edge_factors(nbr_local, valid_local, sol_local, residual,
                            axis=axis)
        emb = embed_sparse_local(params.em, nbr_local, edge, sol_local,
                                 num_layers=num_layers, axis=axis,
                                 kernel=kernel, compute=compute,
                                 gather_impl=gather_impl)
    with jax.named_scope("q.head"):
        return scores_local(params.q, emb, cand_local, axis=axis,
                            masked=masked)


def sparse_policy_scores(params: PolicyParams, g, sol: jax.Array,
                         cand: jax.Array, *, num_layers: int,
                         masked: bool = True, residual=True,
                         kernel: str = "fused", compute: str = "f32",
                         gather_impl: Optional[Callable] = None) -> jax.Array:
    return sparse_local_scores(params, g.neighbors, g.valid, sol, cand,
                               num_layers=num_layers, residual=residual,
                               masked=masked, kernel=kernel, compute=compute,
                               gather_impl=gather_impl)


def sparse_state_bytes(g) -> int:
    """Peak per-step state bytes of the sparse representation (topology +
    masks if ``g`` is a state; topology only for a SparseGraphBatch)."""
    total = g.neighbors.size * 4 + g.valid.size
    if isinstance(g, SparseGraphState):
        total += g.candidate.size * 4 + g.solution.size * 4
    return total
