"""Fused CSR structure2vec layer: edge-tiled gather/segment-sum super-kernel.

The CSR rep stores topology as flat edge arrays (DESIGN.md §13): column ids
``indices`` (B, E), source rows ``row_ids`` (B, E), per-edge residual
factors ``edge_w`` (B, E).  One embedding layer is

    relu(base + θ4 @ segment_sum(x[:, indices] · edge_w, row_ids))

This kernel runs that whole chain in ONE launch per layer, tiled over EDGE
blocks — the CSR analogue of ``s2v_fused.py``'s node-tiled kernels:

- grid (B, E/TE) with the edge axis innermost (sequential); the edge
  arrays enter as (B, 1, E) with (1, 1, TE) blocks, so every block obeys
  the (8, 128) tiling rule for any B;
- node-indexed operands (x, base, and the f32 neighbor-sum accumulator)
  are held whole in VMEM scratch, laid out (C, K, TJ) in C = N/TJ node
  chunks so the kernel walks them with leading-axis indices only; x and
  base are copied in from HBM once per graph and the output copied out
  once, so the VMEM footprint is exactly those three buffers plus the
  edge blocks, whatever XLA does with the operands;
- per edge tile, the gather is Σ_c x[c] @ colsel_c and the segment-sum
  scatter is acc[c] += weighted @ rowsel_cᵀ, where colsel_c/rowsel_c are
  the (TJ, TE) one-hot blocks of the tile's column/row ids against chunk
  c — both contractions run on the MXU.  Padded edge slots carry the
  sentinel column id N (a zero column, or no column) and zero weight —
  doubly inert, so x needs no sentinel column;
- the final edge step applies the fused epilogue relu(base + θ4 @ acc)
  chunk by chunk, so the (B, K, N) neighbor-sum never touches HBM.

Mixed precision follows DESIGN.md §12: ``compute_dtype`` casts the matmul
OPERANDS (x, edge factors, selection matrices, θ4); every accumulation is
f32 via ``preferred_element_type`` and the epilogue stays f32.

The whole-node buffers make the VMEM footprint grow as 12·K·N bytes
(:func:`csr_vmem_bytes`); ``core.s2v_csr`` sends a layer to this kernel
only where that fits ``backend.VMEM_LIMIT_BYTES`` and to the jnp
segment-sum composition otherwise.  ``interpret=None`` compiles on TPU
and interprets elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import (compiler_params, mxu_precision,
                      pipelined_vmem_bytes, resolve_interpret)


def _fused_csr_kernel(t4_ref, idx_ref, row_ref, w_ref, x_hbm, base_hbm,
                      o_hbm, x_v, base_v, acc):
    """Grid (B, E/TE), edge axis innermost (sequential).

    Blocks: idx/row/w (1, 1, TE) [w: cd values held in f32].  x/base/out
    stay in HBM as (B, C, K, TJ) and are copied whole, once per graph,
    into the (C, K, TJ) scratch buffers x_v/base_v; acc (C, K, TJ) f32
    accumulates across the edge axis."""
    bi, ei = pl.program_id(0), pl.program_id(1)
    chunks, k, tj = acc.shape

    @pl.when(ei == 0)
    def _init():
        pltpu.sync_copy(x_hbm.at[bi], x_v)
        pltpu.sync_copy(base_hbm.at[bi], base_v)
        acc[...] = jnp.zeros_like(acc)

    idx = idx_ref[0]                                        # (1, TE) int32
    row = row_ref[0]                                        # (1, TE) int32
    w = w_ref[0]                                            # (1, TE) f32
    te = idx.shape[1]
    cd = x_v.dtype
    ids = jax.lax.broadcasted_iota(jnp.int32, (tj, te), 0)

    def gather(c, g):
        # g[k, t] += Σ_j x[k, j]·[idx[t] = j] over chunk c's node ids
        colsel = (ids + c * tj == idx).astype(cd)           # (TJ, TE)
        return g + jax.lax.dot_general(
            x_v[c], colsel, (((1,), (0,)), ((), ())),
            precision=mxu_precision(cd), preferred_element_type=jnp.float32)

    gathered = jax.lax.fori_loop(0, chunks, gather,
                                 jnp.zeros((k, te), jnp.float32))
    # the cd product rounded once, as the jnp composition rounds it
    weighted = (gathered.astype(cd).astype(jnp.float32) * w).astype(cd)

    def scatter(c, carry):
        # acc[c][k, n] += Σ_t weighted[k, t]·[row[t] = n] — segment-sum
        rowsel = (ids + c * tj == row).astype(cd)           # (TJ, TE)
        acc[c] += jax.lax.dot_general(
            weighted, rowsel, (((1,), (1,)), ((), ())),
            precision=mxu_precision(cd), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, chunks, scatter, 0)

    @pl.when(ei == pl.num_programs(1) - 1)
    def _epilogue():
        def epi(c, carry):
            nbr = acc[c].astype(t4_ref.dtype)      # one rounding, f32 acc
            e3 = jax.lax.dot_general(
                t4_ref[...], nbr, (((1,), (0,)), ((), ())),
                precision=mxu_precision(nbr.dtype),
                preferred_element_type=jnp.float32)
            base_v[c] = jnp.maximum(base_v[c] + e3, 0.0)
            return carry

        jax.lax.fori_loop(0, chunks, epi, 0)
        pltpu.sync_copy(base_v, o_hbm.at[bi])


def _chunked(a, tj):
    """(B, K, N) → (B, N/TJ, K, TJ); N must be a TJ multiple."""
    b, k, n = a.shape
    return a.reshape(b, k, n // tj, tj).transpose(0, 2, 1, 3)


# node chunk: the (TJ, TE) one-hot blocks stay a few dozen vregs
TILE_J = 256


def _csr_tiles(n: int, e: int, tile_e: int):
    """(TE, TJ, padded N): node chunks are lane-dense (128-multiples)."""
    tj = min(TILE_J, -(-n // 128) * 128)
    return min(tile_e, e), tj, -(-n // tj) * tj


def csr_vmem_bytes(k: int, n: int, *, tile_e: int = 256,
                   compute_dtype=jnp.float32) -> int:
    """Scoped VMEM of the CSR kernel at K, N: the whole-node x, base and
    accumulator scratch (12·K·N bytes at f32) plus the edge blocks."""
    te, tj, npad = _csr_tiles(n, tile_e, tile_e)
    f32 = jnp.float32
    node = (npad // tj, k, tj)
    blocks = [((k, k), compute_dtype), ((1, 1, te), jnp.int32),
              ((1, 1, te), jnp.int32), ((1, 1, te), f32)]
    return pipelined_vmem_bytes(
        blocks, [(node, compute_dtype), (node, f32), (node, f32)])


def fused_s2v_layer_csr(theta4: jax.Array, x: jax.Array, indices: jax.Array,
                        row_ids: jax.Array, edge_w: jax.Array,
                        base: jax.Array, *, tile_e: int = 256,
                        compute_dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    """One full CSR embedding layer in a single kernel launch, matching
    ``core.s2v_csr._csr_layer_jnp``.

    theta4:  (K, K) float.
    x:       (B, K, N) float — embeddings, NO sentinel column (padded edge
             slots carry id N and match a zero column or none).
    indices: (B, E) int32 — column ids, sentinel N on padding.
    row_ids: (B, E) int32 — source-row ids (padding rows are don't-care:
             their edge weight is zero).
    edge_w:  (B, E) float — residual-edge factors (0 for padding).
    base:    (B, K, N) float — embed1 + embed2 residual term.
    Returns (B, K, N) float32.
    """
    interpret = resolve_interpret(interpret)
    cd = jnp.dtype(compute_dtype)
    b, k, n = x.shape
    _, e = indices.shape
    te, tj, npad = _csr_tiles(n, e, tile_e)
    pad = (-e) % te
    # padding edges: sentinel column (gathers zero), zero weight, row 0
    indices = jnp.pad(indices, ((0, 0), (0, pad)), constant_values=n)
    row_ids = jnp.pad(row_ids, ((0, 0), (0, pad)))
    edge_w = jnp.pad(edge_w, ((0, 0), (0, pad)))
    epad = e + pad
    chunks = npad // tj
    node_pad = ((0, 0), (0, 0), (0, npad - n))
    x = _chunked(jnp.pad(x.astype(cd), node_pad), tj)
    base = _chunked(jnp.pad(base.astype(jnp.float32), node_pad), tj)

    edge_spec = pl.BlockSpec((1, 1, te), lambda bi, ei: (bi, 0, ei))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    node = (chunks, k, tj)
    out = pl.pallas_call(
        _fused_csr_kernel,
        grid=(b, epad // te),
        in_specs=[pl.BlockSpec((k, k), lambda bi, ei: (0, 0)),
                  edge_spec, edge_spec, edge_spec, hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct((b,) + node, jnp.float32),
        scratch_shapes=[pltpu.VMEM(node, cd), pltpu.VMEM(node, jnp.float32),
                        pltpu.VMEM(node, jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(theta4.astype(cd), indices.astype(jnp.int32)[:, None],
      row_ids.astype(jnp.int32)[:, None],
      edge_w.astype(cd).astype(jnp.float32)[:, None],
      x, base)
    return out.transpose(0, 2, 1, 3).reshape(b, k, npad)[:, :, :n]
