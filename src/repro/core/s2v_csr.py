"""CSR (segment-sum) structure2vec path — flat edge arrays, no padding
(DESIGN.md §13).

The sparse path pads every node's neighbor list to the batch max degree D,
so one power-law hub makes all N rows pay hub-degree padding.  This path
stores the topology as flat CSR arrays ``(indptr, indices, edge_mask)`` and
aggregates with a gather over edge columns followed by a SORTED segment-sum
into rows (row ids are non-decreasing by construction — exploited via
``indices_are_sorted`` instead of a general scatter-add) — storage and
compute are EDGE-proportional, which is what reaches the paper's
N ≥ 1M / 10M+-edge graphs (§6.4).

Topology is immutable, exactly like the sparse rep: a residual edge (u, v)
exists iff the original edge exists and the env's residual rule keeps both
endpoints; per-edge factors are derived from the partial-solution mask S
(:func:`csr_edge_factors`), never by rewriting storage.

``kernel="fused"`` (default) runs each layer as ONE launch — gather →
weight → segment-sum → θ4-matmul → residual add → ReLU — via the Pallas
edge-tiled kernel ``repro.kernels.s2v_csr.fused_s2v_layer_csr`` on TPU
(up to the N where the size rule ``repro.core.s2v.s2v_kernel_fits``
admits it) and the equivalent single XLA composition elsewhere, with the
same layer-0 elision as the other two backends (embed⁰ = 0 ⇒ layer 1 is
relu(embed1+embed2), bit-identical).  ``kernel="xla"`` is the reference
per-op chain.  ``compute="bf16"`` casts gather/matmul operands to bf16
with f32 accumulation (DESIGN.md §12); the segment-sum scatter always
accumulates in f32.

Row ids are derived in-jit from ``indptr`` (:func:`csr_row_ids`) rather
than stored, keeping state bytes at 5·E + ~12·N per graph.

The solve driver lives in ``repro.core.inference`` — use
``solve(..., rep="csr")``; representation dispatch is handled by
``repro.core.graphrep``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .graphs import (CsrGraphBatch, CsrGraphState, csr_batch_from_dense,
                     csr_closed_neighborhood_keep, csr_residual_edge_mask,
                     csr_row_ids, csr_segment_sum)
from .policy import PolicyParams
from .qmodel import scores_local
from .s2v import (check_kernel, compute_dtype, f32_matmuls,
                  s2v_layer_impl)

__all__ = ["CsrGraphBatch", "csr_batch_from_dense", "csr_edge_factors",
           "embed_csr", "embed_csr_local", "csr_policy_scores",
           "csr_state_bytes"]


def csr_edge_factors(indices: jax.Array, edge_mask: jax.Array,
                     row_ids: jax.Array, sol: jax.Array,
                     residual) -> jax.Array:
    """(B, E) per-edge factors for the env's residual mode
    (``env.register``): ``True``/"solution" → S's edges removed;
    ``"closed"`` → S's and its neighbors' edges removed (MIS);
    ``False``/"none" → the original topology (MaxCut/MDS)."""
    if residual is False or residual == "none":
        return edge_mask.astype(jnp.float32)
    if residual == "closed":
        keep = csr_closed_neighborhood_keep(indices, edge_mask, row_ids, sol)
        keep_pad = jnp.pad(keep, ((0, 0), (0, 1)))           # sentinel slot
        keep_col = jax.vmap(lambda kb, ib: kb[ib])(keep_pad, indices)
        keep_row = jax.vmap(lambda kb, rb: kb[rb])(keep, row_ids)
        return edge_mask.astype(jnp.float32) * keep_col * keep_row
    return csr_residual_edge_mask(indices, edge_mask, row_ids, sol)


def _gather_cols(x: jax.Array, indices: jax.Array) -> jax.Array:
    """x (B, K, N+1) [zero-padded], indices (B, E) → (B, K, E)."""
    return jax.vmap(lambda xb, ib: xb[:, ib])(x, indices)


def _segment_rows(weighted: jax.Array, row_ids: jax.Array,
                  n: int) -> jax.Array:
    """(B, K, E) edge values → (B, K, N) per-row sums via SORTED
    segment-sum: CSR row ids are non-decreasing by construction, and the
    (E, K) leading-segment-axis layout reduces contiguous runs instead of
    scatter-adding along the trailing axis — measurably faster on CPU
    (the ROADMAP 1a scatter-bound gap; delta recorded per eval in
    `benchmarks/sparse_vs_dense.py`) and bit-identical to the scatter."""
    def one(wb, rb):
        return jax.ops.segment_sum(wb.T, rb, num_segments=n,
                                   indices_are_sorted=True).T
    return jax.vmap(one)(weighted, row_ids)


@f32_matmuls
def _csr_layer_jnp(theta4, x_full, indices, row_ids, edge_w, base, cd):
    """One fused CSR layer as a single XLA composition: gather edge columns
    with cd-cast operands, weight, segment-sum into rows with f32
    accumulation, θ4-matmul, residual + ReLU.  x_full (B, K, N) has NO
    sentinel column (padded ids select the zero column appended here)."""
    xp = jnp.pad(x_full, ((0, 0), (0, 0), (0, 1))).astype(cd)
    gathered = _gather_cols(xp, indices)                    # (B, K, E)
    weighted = (gathered * edge_w[:, None, :].astype(cd)).astype(jnp.float32)
    n = x_full.shape[-1]
    nbr = _segment_rows(weighted, row_ids, n)               # (B, K, N)
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(cd), nbr.astype(cd),
                    preferred_element_type=jnp.float32)
    return jax.nn.relu(base + e3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _csr_layer_hw(theta4, x_full, indices, row_ids, edge_w, base, cd):
    from ..kernels.ops import fused_s2v_layer_csr
    return fused_s2v_layer_csr(theta4, x_full, indices, row_ids, edge_w,
                               base, compute_dtype=cd)


def _csr_layer_hw_fwd(theta4, x_full, indices, row_ids, edge_w, base, cd):
    return _csr_layer_hw(theta4, x_full, indices, row_ids, edge_w, base,
                         cd), (theta4, x_full, indices, row_ids, edge_w, base)


def _csr_layer_hw_bwd(cd, res, g):
    theta4, x_full, indices, row_ids, edge_w, base = res
    _, vjp = jax.vjp(
        lambda t4, x, ew, b: _csr_layer_jnp(t4, x, indices, row_ids, ew, b,
                                            cd),
        theta4, x_full, edge_w, base)
    dt4, dx, dew, db = vjp(g)
    return dt4, dx, None, None, dew, db


_csr_layer_hw.defvjp(_csr_layer_hw_fwd, _csr_layer_hw_bwd)


def _csr_layer_fused(theta4, x_full, indices, row_ids, edge_w, base, cd):
    """Dispatch for one fused CSR layer by the size rule
    (:func:`repro.core.s2v.s2v_layer_impl`): the Pallas edge-tiled kernel
    on TPU while its whole-(K, N) buffers fit VMEM, the jnp segment-sum
    composition otherwise."""
    if s2v_layer_impl("csr", k=x_full.shape[1], n=x_full.shape[2],
                      compute_dtype=cd) == "pallas":
        return _csr_layer_hw(theta4, x_full, indices, row_ids, edge_w,
                             base, cd)
    return _csr_layer_jnp(theta4, x_full, indices, row_ids, edge_w, base, cd)


@f32_matmuls
def embed_csr_local(params, indices: jax.Array, row_ids: jax.Array,
                    edge_w: jax.Array, sol: jax.Array, *, num_layers: int,
                    kernel: str = "fused", compute: str = "f32") -> jax.Array:
    """structure2vec over the residual graph implied by (topology, S) on
    flat CSR arrays.  indices (B, E) int32 column ids (sentinel N on
    padding); row_ids (B, E) int32 source rows; edge_w (B, E) residual-edge
    factors; sol (B, N).  Returns (B, K, N).

    CSR has no spatial (sp > 1) path yet — the engine fails fast before
    reaching here (DESIGN.md §13)."""
    check_kernel(kernel)
    cd = compute_dtype(compute)
    b, n = sol.shape
    k = params.theta1.shape[0]

    deg = csr_segment_sum(edge_w, row_ids, n)               # residual degree
    embed1 = params.theta1[None, :, None] * sol[:, None, :]
    w = jax.nn.relu(params.theta2[None, :, None] * deg[:, None, :])
    embed2 = jnp.einsum("kj,bjn->bkn", params.theta3, w)
    base = embed1 + embed2                                  # f32 residual

    embed = jnp.zeros((b, k, n), jnp.float32)
    for layer in range(num_layers):
        if kernel == "fused":
            if layer == 0:
                # embed⁰ = 0 ⇒ the first aggregation is exactly zero ⇒
                # layer 1 is relu(base), bit-identical.
                embed = jax.nn.relu(base)
                continue
            embed = _csr_layer_fused(params.theta4, embed, indices, row_ids,
                                     edge_w, base, cd)
            continue
        # Reference "xla" per-op chain (semantics of record).
        xp = jnp.pad(embed, ((0, 0), (0, 0), (0, 1)))       # sentinel col
        gathered = _gather_cols(xp, indices)                # (B, K, E)
        weighted = gathered * edge_w[:, None, :]
        nbr = _segment_rows(weighted, row_ids, n)
        embed3 = jnp.einsum("kj,bjn->bkn", params.theta4, nbr)
        embed = jax.nn.relu(base + embed3)
    return embed


def embed_csr(params, g, sol: jax.Array, *, num_layers: int, residual=True,
              kernel: str = "fused", compute: str = "f32") -> jax.Array:
    """Convenience wrapper: derives row ids and the edge factors for the
    env's ``residual`` mode from (topology, S) and embeds all N nodes.
    ``g`` is anything carrying ``indptr``/``indices``/``edge_mask`` — a
    CsrGraphBatch or CsrGraphState."""
    row_ids = csr_row_ids(g.indptr, g.indices.shape[1])
    edge_w = csr_edge_factors(g.indices, g.edge_mask, row_ids, sol, residual)
    return embed_csr_local(params, g.indices, row_ids, edge_w, sol,
                           num_layers=num_layers, kernel=kernel,
                           compute=compute)


def csr_policy_scores(params: PolicyParams, g, sol: jax.Array,
                      cand: jax.Array, *, num_layers: int,
                      masked: bool = True, residual=True,
                      kernel: str = "fused",
                      compute: str = "f32") -> jax.Array:
    with jax.named_scope("s2v.embed"):
        emb = embed_csr(params.em, g, sol, num_layers=num_layers,
                        residual=residual, kernel=kernel, compute=compute)
    with jax.named_scope("q.head"):
        return scores_local(params.q, emb, cand, masked=masked)


def csr_state_bytes(g) -> int:
    """Peak per-step state bytes of the CSR representation: 5·E + 4·(N+1)
    for the topology, plus the 8·N C/S masks if ``g`` is a state.  The
    edge-proportional formula of DESIGN.md §13 — no N² term, no N·maxdeg
    term."""
    total = g.indices.size * 4 + g.edge_mask.size + g.indptr.size * 4
    if isinstance(g, CsrGraphState):
        total += g.candidate.size * 4 + g.solution.size * 4
    return total
