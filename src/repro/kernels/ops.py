"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` compiles on TPU and interprets on every other backend
(``backend.resolve_interpret``).  All wrappers accept/return the same
shapes as their ``ref.py`` oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .backend import resolve_interpret
from .s2v_fused import (fused_s2v_layer as _fused_s2v_layer,
                        fused_s2v_layer_sparse as _fused_s2v_layer_sparse,
                        mp_aggregate as _mp_aggregate)
from .s2v_csr import fused_s2v_layer_csr as _fused_s2v_layer_csr
from .s2v_gather import sparse_mp_aggregate as _sparse_mp_aggregate
from .wkv6 import wkv6_chunked as _wkv6_chunked
from .swa import swa_attention as _swa_attention
from .moe_gemm import grouped_glu_ffn as _grouped_glu_ffn


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_l",
                                             "compute_dtype", "interpret"))
def fused_s2v_layer(theta4, embed, adj, base, *, tile_n: int | None = None,
                    tile_l: int | None = None, compute_dtype=jnp.float32,
                    interpret: bool | None = None):
    """Fused dense structure2vec layer (Alg. 2 lines 11+13-14, one launch)."""
    return _fused_s2v_layer(theta4, embed, adj, base, tile_n=tile_n,
                            tile_l=tile_l, compute_dtype=compute_dtype,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_n", "compute_dtype",
                                             "interpret"))
def fused_s2v_layer_sparse(theta4, x, neighbors, edge, base, *,
                           tile_n: int = 128, compute_dtype=jnp.float32,
                           interpret: bool | None = None):
    """Fused sparse (padded edge-list) structure2vec layer, one launch."""
    return _fused_s2v_layer_sparse(theta4, x, neighbors, edge, base,
                                   tile_n=tile_n, compute_dtype=compute_dtype,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_e", "compute_dtype",
                                             "interpret"))
def fused_s2v_layer_csr(theta4, x, indices, row_ids, edge_w, base, *,
                        tile_e: int = 256, compute_dtype=jnp.float32,
                        interpret: bool | None = None):
    """Fused CSR (flat edge-array) structure2vec layer, one launch."""
    return _fused_s2v_layer_csr(theta4, x, indices, row_ids, edge_w, base,
                                tile_e=tile_e, compute_dtype=compute_dtype,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_l",
                                             "compute_dtype", "interpret"))
def mp_aggregate(embed, adj, *, tile_n: int | None = None,
                 tile_l: int | None = None, compute_dtype=jnp.float32,
                 interpret: bool | None = None):
    """Aggregation-only partial kernel for the sharded dense path (the psum
    between aggregate and epilogue splits the fusion at the collective)."""
    return _mp_aggregate(embed, adj, tile_n=tile_n, tile_l=tile_l,
                         compute_dtype=compute_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def sparse_mp_aggregate(x, neighbors, edge, *, tile_n: int = 128,
                        interpret: bool | None = None):
    """Sparse (padded edge-list) s2v neighbor aggregation (gather kernel)."""
    return _sparse_mp_aggregate(x, neighbors, edge, tile_n=tile_n,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 64, interpret: bool | None = None):
    """Chunked RWKV6 recurrence. Returns (out, final_state)."""
    interpret = resolve_interpret(interpret)
    return _wkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "tile_q", "tile_k", "interpret"))
def swa(q, k, v, *, window: int, tile_q: int = 128, tile_k: int = 128,
        interpret: bool | None = None):
    """Sliding-window causal flash attention."""
    interpret = resolve_interpret(interpret)
    return _swa_attention(q, k, v, window=window, tile_q=tile_q,
                          tile_k=tile_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_c", "tile_d", "tile_f",
                                              "interpret"))
def grouped_glu_ffn(x, wg, wu, wo, *, tile_c: int = 128, tile_d: int = 128,
                    tile_f: int = 128, interpret: bool | None = None):
    """Grouped per-expert GLU FFN (MoE hotspot)."""
    interpret = resolve_interpret(interpret)
    return _grouped_glu_ffn(x, wg, wu, wo, tile_c=tile_c, tile_d=tile_d,
                            tile_f=tile_f, interpret=interpret)


# re-export oracles for convenience
ref = _ref
