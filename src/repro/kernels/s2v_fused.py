"""Fused structure2vec LAYER super-kernels (paper Alg. 2, one launch/layer).

The paper's per-step cost is dominated by Alg. 2's message-passing chain:
neighbor aggregation (line 11) → θ4 projection → residual add → ReLU
(lines 13-14).  The GPU original runs this as cuSPARSE SpMM + separate
cuBLAS/elementwise ops; here each GraphRep backend gets ONE VMEM-tiled
Pallas kernel per layer instead of a chain of XLA ops:

- ``fused_s2v_layer``:        dense rep — blocked batched (K,Nl)×(Nl,N)
  aggregation accumulating into a VMEM f32 scratch, with the θ4-matmul +
  residual + ReLU epilogue emitted by the final reduction step of each
  output tile.  The (B, K, N) neighbor-sum tensor never touches HBM.
- ``fused_s2v_layer_sparse``: sparse rep — the (TJ, TN) one-hot tile step
  of ``s2v_gather.py`` (neighbor lists expanded on-chip into selection
  blocks, aggregation as x @ M on the MXU), then the same fused epilogue.
  Sentinel-free: padded neighbor ids equal N, which matches no one-hot
  row in [0, N), so x needs no sentinel column.
- ``mp_aggregate``:           aggregation-only partial kernel for the
  spatially-sharded dense path, where the cross-device psum (Alg. 2
  line 12) must run between aggregation and epilogue and therefore splits
  the fusion at the collective boundary.

Mixed precision: ``compute_dtype`` casts the matmul OPERANDS (embeddings,
adjacency/edge factors, θ4); every accumulation is f32 via
``preferred_element_type`` and the residual add + ReLU epilogue stays f32.
Params remain f32 masters — casts happen at use (DESIGN.md §12).

The dense kernels' blocks are chosen from the shapes (:func:`dense_tiles`):
whole rows of the adjacency where they fit, and as many rows as fit a VMEM
budget well inside the kernel limit, so one grid step moves several MiB.
Their grids cover N and Nl with ``pl.cdiv`` and read the adjacency as it
is, unpadded: the last, partial block along Nl is masked in the kernel.
The sparse kernel's tiles default to 128 and are clamped for small
problems; its blocks are bounded by the tiles and the max degree D, so
``sparse_vmem_bytes`` never grows with N.  ``interpret=None`` compiles on
TPU and interprets elsewhere (``backend.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import (compiler_params, mxu_precision,
                      pipelined_vmem_bytes, resolve_interpret)
from .s2v_gather import sparse_layout, sparse_tile_step


# VMEM the shape-chosen dense blocks may take, as ``dense_vmem_bytes``
# counts it: room for full-width 128-row f32 blocks at N=21,000 (34 MiB),
# with the rest of the kernel limit left to Mosaic's internal scratch.
DENSE_VMEM_BUDGET = 40 * 2**20


def dense_tiles(k: int, n: int, nl: int, *, epilogue: bool,
                compute_dtype=jnp.float32) -> tuple[int, int]:
    """The (tile_n, tile_l) blocks of a dense kernel, from its shapes.

    Each is the full dim or a multiple of 128 (the lane dim of the
    adjacency, output and embedding blocks), and together they keep
    ``dense_vmem_bytes`` within ``DENSE_VMEM_BUDGET``.  tile_n is the
    whole width N where 128 rows of it fit, else the widest multiple of
    128 that does: whole rows make one contiguous DMA per block and leave
    no ragged edge along N.  tile_l is then the most rows that fit, all
    of Nl where they do, so small graphs (the train and serving shapes)
    take one block per batch element and large ones several MiB of
    adjacency per grid step."""
    def fits(tn, tl):
        return dense_vmem_bytes(k, epilogue=epilogue, tile_n=tn, tile_l=tl,
                                compute_dtype=compute_dtype) \
            <= DENSE_VMEM_BUDGET

    tn, tl = n, nl
    while tn > 128 and not fits(tn, min(tl, 128)):
        tn = (tn - 1) // 128 * 128          # the next lower multiple of 128
    while tl > 128 and not fits(tn, tl):
        tl = (tl - 1) // 128 * 128
    return tn, tl


def _blocks(k, n, nl, tile_n, tile_l, cd, *, epilogue):
    """(tn, tl): the explicit tiles where given, else the shape-chosen
    ones, clamped to the dims."""
    tn, tl = dense_tiles(k, n, nl, epilogue=epilogue, compute_dtype=cd)
    return min(tile_n or tn, n), min(tile_l or tl, nl)


def _accumulate(e_ref, a_ref, acc, nl: int):
    """acc += e (K, TL) @ a (TL, TN) for this step of the l axis.

    The grid covers Nl with ``pl.cdiv``, so the last l block may run past
    Nl, where the block holds undefined values (NaN in interpret mode).
    On that step the embedding columns AND the adjacency rows at or past
    Nl are zeroed before the dot: both, since 0·NaN is NaN.  Columns past
    N need no mask: they reach only output columns that the writeback
    drops."""
    def dot(e, a):
        acc[...] += jax.lax.dot_general(
            e, a, (((1,), (0,)), ((), ())), precision=mxu_precision(e.dtype),
            preferred_element_type=jnp.float32)

    rem = nl % e_ref.shape[-1]             # valid rows of the last l block
    if not rem:
        dot(e_ref[0], a_ref[0])
        return
    l, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(l < last)
    def _full():
        dot(e_ref[0], a_ref[0])

    @pl.when(l == last)
    def _edge():
        e, a = e_ref[0], a_ref[0]
        col = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        dot(jnp.where(col < rem, e, 0), jnp.where(row < rem, a, 0))


def _fused_dense_kernel(t4_ref, e_ref, a_ref, base_ref, o_ref, acc, *,
                        nl: int):
    """Grid (B, ⌈N/TN⌉, ⌈Nl/TL⌉), reduction axis l innermost (sequential).

    e (1,K,TL) @ a (1,TL,TN) accumulates into the f32 VMEM scratch; the
    last l step applies the fused epilogue relu(base + θ4 @ acc) so the
    neighbor-sum tile never round-trips through HBM."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    _accumulate(e_ref, a_ref, acc, nl)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        nbr = acc[...].astype(t4_ref.dtype)        # one rounding, f32 acc
        e3 = jax.lax.dot_general(t4_ref[...], nbr, (((1,), (0,)), ((), ())),
                                 precision=mxu_precision(nbr.dtype),
                                 preferred_element_type=jnp.float32)
        o_ref[0] = jnp.maximum(base_ref[0] + e3, 0.0)


def fused_s2v_layer(theta4: jax.Array, embed: jax.Array, adj: jax.Array,
                    base: jax.Array, *, tile_n: int | None = None,
                    tile_l: int | None = None, compute_dtype=jnp.float32,
                    interpret: bool | None = None) -> jax.Array:
    """One full dense embedding layer in a single kernel launch:
    relu(base + θ4 @ (embed @ adj)), matching ``ref.s2v_layer``.

    embed (B, K, Nl), adj (B, Nl, N), base (B, K, N) — no collective; the
    sharded path uses :func:`mp_aggregate` and fuses only up to the psum.
    ``tile_n``/``tile_l`` override the shape-chosen :func:`dense_tiles`.
    """
    interpret = resolve_interpret(interpret)
    cd = jnp.dtype(compute_dtype)
    b, k, nl = embed.shape
    _, _, n = adj.shape
    tn, tl = _blocks(k, n, nl, tile_n, tile_l, cd, epilogue=True)

    return pl.pallas_call(
        functools.partial(_fused_dense_kernel, nl=nl),
        grid=(b, pl.cdiv(n, tn), pl.cdiv(nl, tl)),
        in_specs=[
            pl.BlockSpec((k, k), lambda bi, ni, li: (0, 0)),
            pl.BlockSpec((1, k, tl), lambda bi, ni, li: (bi, 0, li)),
            pl.BlockSpec((1, tl, tn), lambda bi, ni, li: (bi, li, ni)),
            pl.BlockSpec((1, k, tn), lambda bi, ni, li: (bi, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, k, tn), lambda bi, ni, li: (bi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((b, k, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(theta4.astype(cd), embed.astype(cd), adj.astype(cd),
      base.astype(jnp.float32))


def _agg_kernel(e_ref, a_ref, o_ref, acc, *, nl: int):
    """Grid (B, ⌈N/TN⌉, ⌈Nl/TL⌉). e (1,K,TL) @ a (1,TL,TN) accumulated
    over l."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    _accumulate(e_ref, a_ref, acc, nl)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = acc[...]


def mp_aggregate(embed: jax.Array, adj: jax.Array, *,
                 tile_n: int | None = None, tile_l: int | None = None,
                 compute_dtype=jnp.float32,
                 interpret: bool | None = None) -> jax.Array:
    """nbr[b,k,n] = Σ_l embed[b,k,l]·adj[b,l,n] with VMEM-blocked tiles.

    Aggregation-only partial of :func:`fused_s2v_layer` for the sharded
    dense path: the f32 partial sums feed the cross-device psum, keeping
    cross-mesh numerics identical to the single-device fused layer."""
    interpret = resolve_interpret(interpret)
    cd = jnp.dtype(compute_dtype)
    b, k, nl = embed.shape
    _, _, n = adj.shape
    tn, tl = _blocks(k, n, nl, tile_n, tile_l, cd, epilogue=False)

    return pl.pallas_call(
        functools.partial(_agg_kernel, nl=nl),
        grid=(b, pl.cdiv(n, tn), pl.cdiv(nl, tl)),
        in_specs=[
            pl.BlockSpec((1, k, tl), lambda bi, ni, li: (bi, 0, li)),
            pl.BlockSpec((1, tl, tn), lambda bi, ni, li: (bi, li, ni)),
        ],
        out_specs=pl.BlockSpec((1, k, tn), lambda bi, ni, li: (bi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((b, k, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(embed.astype(cd), adj.astype(cd))


def dense_vmem_bytes(k: int, *, epilogue: bool, tile_n: int, tile_l: int,
                     compute_dtype=jnp.float32) -> int:
    """Scoped VMEM of a dense kernel (fused layer or ``mp_aggregate``):
    (K, TL) embedding, (TL, TN) adjacency and (K, TN) output blocks (+ θ4
    and base with the epilogue) and the (K, TN) accumulator."""
    f32 = jnp.float32
    blocks = [((1, k, tile_l), compute_dtype),
              ((1, tile_l, tile_n), compute_dtype), ((1, k, tile_n), f32)]
    if epilogue:
        blocks += [((k, k), compute_dtype), ((1, k, tile_n), f32)]
    return pipelined_vmem_bytes(blocks, [((k, tile_n), f32)])


def _fused_sparse_kernel(t4_ref, nbr_ref, edge_ref, x_ref, base_ref, o_ref,
                         acc):
    """Grid (B, Nl/TN, N/TJ), source-node axis j innermost (sequential).

    The shared one-hot tile step accumulates the (K, TN) neighbor sum; the
    last j step applies the fused θ4 + residual + ReLU epilogue."""
    sparse_tile_step(nbr_ref, edge_ref, x_ref, acc)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        e3 = jax.lax.dot_general(
            t4_ref[...], acc[...].astype(t4_ref.dtype),
            (((1,), (0,)), ((), ())), precision=mxu_precision(t4_ref.dtype),
            preferred_element_type=jnp.float32)
        o_ref[0] = jnp.maximum(base_ref[0] + e3, 0.0)


def fused_s2v_layer_sparse(theta4: jax.Array, x: jax.Array,
                           neighbors: jax.Array, edge: jax.Array,
                           base: jax.Array, *, tile_n: int = 128,
                           compute_dtype=jnp.float32,
                           interpret: bool | None = None) -> jax.Array:
    """One full sparse embedding layer in a single kernel launch, matching
    ``ref.s2v_layer_sparse``.

    x:         (B, K, N) float — embeddings, NO sentinel column (padded
               neighbor ids equal N and match no one-hot row).
    neighbors: (B, Nl, D) int32 — padded neighbor ids (sentinel N).
    edge:      (B, Nl, D) float — residual-edge factors (0 for padding).
    base:      (B, K, Nl) float — embed1 + embed2 residual term.
    Returns (B, K, Nl) float32.
    """
    interpret = resolve_interpret(interpret)
    cd = jnp.dtype(compute_dtype)
    b, k, _ = x.shape
    _, nl, d = neighbors.shape
    x, nbr_t, edge_t, tn, tj = sparse_layout(
        x.astype(cd), neighbors, edge, tile_n=tile_n)
    nlpad, nxpad = nbr_t.shape[2], x.shape[2]
    # padding nodes have zero edge weight and zero base → relu(0) = 0
    base = jnp.pad(base.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, nlpad - nl)))

    out = pl.pallas_call(
        _fused_sparse_kernel,
        grid=(b, nlpad // tn, nxpad // tj),
        in_specs=[
            pl.BlockSpec((k, k), lambda bi, ni, ji: (0, 0)),
            pl.BlockSpec((1, d, tn), lambda bi, ni, ji: (bi, 0, ni)),
            pl.BlockSpec((1, d, tn), lambda bi, ni, ji: (bi, 0, ni)),
            pl.BlockSpec((1, k, tj), lambda bi, ni, ji: (bi, 0, ji)),
            pl.BlockSpec((1, k, tn), lambda bi, ni, ji: (bi, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, k, tn), lambda bi, ni, ji: (bi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((b, k, nlpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(theta4.astype(cd), nbr_t, edge_t, x, base)
    return out[:, :, :nl]
