"""Pallas TPU kernel for the SPARSE structure2vec neighbor aggregation —
the hot loop of the padded edge-list path (paper §4.1/§5.2, DESIGN.md §1/§2):

    nbr_sum[b, k, i] = Σ_d  x[b, k, neighbors[b, i, d]] · edge[b, i, d]

where ``x`` is the (B, K, N+1) embedding buffer with a zero sentinel column
and ``edge`` carries the residual-edge factors (valid ∧ keep[u] ∧ keep[v]).

The GPU original uses cuSPARSE COO SpMM; TPUs have no efficient gather along
the lane dimension, so the kernel restructures the gather as an on-chip
one-hot expansion + MXU matmul (DESIGN.md §2).  The grid is
(B, N/TN, N/TJ) with the source-node axis j innermost: for each
(TJ, TN) tile it builds the selection block
M[j, i] = Σ_d edge[i, d]·[neighbors[i, d] = j] in vector registers and
accumulates x[:, j-tile] @ M into a (K, TN) f32 scratch.  The neighbor
lists are laid out (B, D, N) inside the wrapper so that each d reads one
(1, TN) row of the block — a sublane slice, never a lane slice — and every
block is bounded by the tiles and D, never by N.

This standalone aggregation serves the reference "xla" chain on TPU; the
production path fuses the same tile step with the θ4 + residual + ReLU
epilogue in ``s2v_fused.fused_s2v_layer_sparse``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import (compiler_params, mxu_precision,
                      pipelined_vmem_bytes, resolve_interpret)


def sparse_tile_step(nbr_ref, edge_ref, x_ref, acc):
    """One (b, i-tile, j-tile) grid step shared by the sparse kernels.

    Blocks: nbr (1, D, TN) int32, edge (1, D, TN) f32, x (1, K, TJ);
    acc (K, TN) f32 scratch, zeroed on the first j step."""
    j = pl.program_id(2)
    _, dmax, tn = nbr_ref.shape
    tj = x_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    ids = jax.lax.broadcasted_iota(jnp.int32, (tj, tn), 0) + j * tj

    def body(d, m):
        hit = ids == nbr_ref[0, pl.ds(d, 1), :]               # (TJ, TN)
        return m + jnp.where(hit, edge_ref[0, pl.ds(d, 1), :], 0.0)

    sel = jax.lax.fori_loop(0, dmax, body,
                            jnp.zeros((tj, tn), jnp.float32))
    # acc[k, i] += Σ_j x[k, j] · M[j, i] — MXU contraction over the j tile
    acc[...] += jax.lax.dot_general(
        x_ref[0], sel.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)


# source-node tile: the (TJ, TN) selection block stays in 16 f32 vregs
TILE_J = 128


def sparse_layout(x, neighbors, edge, *, tile_n: int):
    """Pad and transpose the sparse kernels' operands to their grid layout.

    Returns ``(x, nbr_t, edge_t, tn, tj)``: x (B, K, Nx) padded with zero
    columns to a TJ multiple; neighbors/edge transposed to (B, D, Nl)
    and padded to a TN multiple with the id ``Nx`` (matches no column of
    the padded x) and zero weight, so padding rows aggregate to zero."""
    b, k, nx = x.shape
    _, nl, _ = neighbors.shape
    tn, tj = min(tile_n, nl), min(TILE_J, nx)
    pn, pj = (-nl) % tn, (-nx) % tj
    x = jnp.pad(x, ((0, 0), (0, 0), (0, pj)))
    nbr_t = jnp.pad(neighbors.astype(jnp.int32).swapaxes(1, 2),
                    ((0, 0), (0, 0), (0, pn)), constant_values=nx + pj)
    edge_t = jnp.pad(edge.astype(jnp.float32).swapaxes(1, 2),
                     ((0, 0), (0, 0), (0, pn)))
    return x, nbr_t, edge_t, tn, tj


def sparse_vmem_bytes(k: int, max_degree: int, *, epilogue: bool,
                      tile_n: int = 128, compute_dtype=jnp.float32) -> int:
    """Scoped VMEM of a sparse kernel: (D, TN) neighbor/edge blocks,
    (K, TJ) x block, (K, TN) output (+ base and θ4 with the epilogue)
    and the (K, TN) accumulator — independent of N."""
    f32 = jnp.float32
    blocks = [((1, max_degree, tile_n), jnp.int32),
              ((1, max_degree, tile_n), f32),
              ((1, k, TILE_J), compute_dtype), ((1, k, tile_n), f32)]
    if epilogue:
        blocks += [((k, k), compute_dtype), ((1, k, tile_n), f32)]
    return pipelined_vmem_bytes(blocks, [((k, tile_n), f32)])


def _sparse_agg_kernel(nbr_ref, edge_ref, x_ref, o_ref, acc):
    sparse_tile_step(nbr_ref, edge_ref, x_ref, acc)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = acc[...]


def sparse_mp_aggregate(x: jax.Array, neighbors: jax.Array,
                        edge: jax.Array, *, tile_n: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """Gather-based sparse message passing, tiled through VMEM.

    x:         (B, K, N+1) float — embeddings, zero sentinel column at N.
    neighbors: (B, N, D) int32 — padded neighbor ids (sentinel N).
    edge:      (B, N, D) float — residual-edge factors (0 for padding).
    Returns (B, K, N) float32, matching ``ref.sparse_mp_aggregate``.
    """
    interpret = resolve_interpret(interpret)
    b, k, _ = x.shape
    _, n, d = neighbors.shape
    x, nbr_t, edge_t, tn, tj = sparse_layout(
        x.astype(jnp.float32), neighbors, edge, tile_n=tile_n)
    npad, nxpad = nbr_t.shape[2], x.shape[2]

    out = pl.pallas_call(
        _sparse_agg_kernel,
        grid=(b, npad // tn, nxpad // tj),
        in_specs=[
            pl.BlockSpec((1, d, tn), lambda bi, ni, ji: (bi, 0, ni)),
            pl.BlockSpec((1, d, tn), lambda bi, ni, ji: (bi, 0, ni)),
            pl.BlockSpec((1, k, tj), lambda bi, ni, ji: (bi, 0, ji)),
        ],
        out_specs=pl.BlockSpec((1, k, tn), lambda bi, ni, ji: (bi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((b, k, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(nbr_t, edge_t, x)
    return out[:, :, :n]
