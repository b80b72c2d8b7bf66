"""structure2vec graph embedding model (paper Eq. 1, Alg. 2).

``embed_local`` implements Alg. 2 exactly: each device computes embeddings for
its N/P resident nodes from its (B, N/P, N) adjacency row-block, with one
all-reduce of a (B, K, N) buffer per embedding layer (paper: MPI_All_reduce;
here: ``jax.lax.psum`` when ``axis`` names a shard_map mesh axis, or a no-op
in the single-device path ``axis=None``).

Kernel selection (``kernel=``, DESIGN.md §12):

- ``"fused"`` (default): one fused launch per layer — aggregate → θ4-matmul
  → residual add → ReLU — as the Pallas super-kernel on TPU
  (``repro.kernels.s2v_fused``, wrapped in a custom_vjp whose backward runs
  the jnp composition) and as the equivalent single XLA composition
  elsewhere, or on TPU where the size rule :func:`s2v_kernel_fits` finds
  the kernel too large for VMEM.  The fused path also elides layer 0
  entirely: embeddings initialize to zero (Alg. 2 line 3), so the first
  aggregation is exactly zero and layer 1 reduces to relu(embed1 +
  embed2) — bit-identical, half the aggregation work at L=2, and one
  collective fewer per eval when sharded.
- ``"xla"``: the reference per-op chain, kept for parity tests and as the
  semantics of record.

``compute=`` selects the matmul operand precision: ``"f32"`` (default) or
``"bf16"`` (operands cast at use, f32 accumulation, f32 residual/ReLU, f32
master params — see DESIGN.md §12).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.backend import VMEM_LIMIT_BYTES, on_tpu
from ..kernels.s2v_csr import csr_vmem_bytes
from ..kernels.s2v_fused import dense_tiles, dense_vmem_bytes
from ..kernels.s2v_gather import sparse_vmem_bytes

KERNELS = ("fused", "xla")
COMPUTE_MODES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def compute_dtype(compute: str):
    """Resolve a ``PolicyConfig.compute`` mode name to the operand dtype."""
    try:
        return COMPUTE_MODES[compute]
    except KeyError:
        raise ValueError(f"unknown compute mode {compute!r}; "
                         f"available: {sorted(COMPUTE_MODES)}") from None


def check_kernel(kernel: str) -> str:
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; available: {KERNELS}")
    return kernel


def f32_matmuls(fn):
    """Trace ``fn``'s matmuls at full f32 precision.

    XLA's DEFAULT precision on TPU rounds f32 matmul operands to bf16;
    ``compute="f32"`` promises f32 operands, so the S2V and Q functions
    run under the "float32" setting.  bf16-cast operands stay bf16, and
    CPU matmuls are f32 either way."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return wrapped


def s2v_kernel_fits(rep: str, *, k: int, n: int = 0, nl: int = 0,
                    max_degree: int = 0, compute_dtype=jnp.float32,
                    aggregate_only: bool = False) -> bool:
    """The S2V size rule (ROADMAP S2(a)): True iff the Pallas layer kernel
    of ``rep`` fits the scoped-VMEM budget ``VMEM_LIMIT_BYTES`` at these
    shapes.  The dense kernels are tiled over both node axes, in the
    blocks that ``dense_tiles`` picks from K, N and the local rows Nl
    (``nl``, default N); the sparse kernel's footprint moves with K and
    the max degree D; the CSR kernel holds whole (K, N) panels, so its
    bound is on N.  ``aggregate_only`` selects the aggregation-only
    kernels (dense ``mp_aggregate``, the sparse gather)."""
    epilogue = not aggregate_only
    if rep == "dense":
        tn, tl = dense_tiles(k, n, nl or n, epilogue=epilogue,
                             compute_dtype=compute_dtype)
        need = dense_vmem_bytes(k, epilogue=epilogue, tile_n=tn,
                                tile_l=tl, compute_dtype=compute_dtype)
    elif rep == "sparse":
        need = sparse_vmem_bytes(k, max_degree, epilogue=epilogue,
                                 compute_dtype=compute_dtype)
    elif rep == "csr":
        need = csr_vmem_bytes(k, n, compute_dtype=compute_dtype)
    else:
        raise ValueError(f"unknown graph rep {rep!r}")
    return need <= VMEM_LIMIT_BYTES


def s2v_layer_impl(rep: str, **shapes) -> str:
    """Which lowering a fused S2V layer takes: ``"pallas"`` (the kernel)
    on a TPU where :func:`s2v_kernel_fits`, else ``"xla"`` (the same layer
    as one XLA composition)."""
    return ("pallas" if on_tpu() and s2v_kernel_fits(rep, **shapes)
            else "xla")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class S2VParams:
    """theta1..theta4 of Eq. 1 (embedding) — theta5..7 live in qmodel."""
    theta1: jax.Array  # (K,)
    theta2: jax.Array  # (K,)
    theta3: jax.Array  # (K, K)
    theta4: jax.Array  # (K, K)

    @property
    def dim(self) -> int:
        return self.theta1.shape[0]


def init_s2v(key: jax.Array, k: int, scale: float = 0.1) -> S2VParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return S2VParams(
        theta1=jax.random.normal(k1, (k,)) * scale,
        theta2=jax.random.normal(k2, (k,)) * scale,
        theta3=jax.random.normal(k3, (k, k)) * (scale / jnp.sqrt(k)),
        theta4=jax.random.normal(k4, (k, k)) * (scale / jnp.sqrt(k)),
    )


# ---------------------------------------------------------------------------
# Fused-layer lowerings.  The jnp composition is the differentiable
# semantics of record; the Pallas super-kernel carries a custom_vjp whose
# backward differentiates the jnp composition (identical math, so the
# recomputed ReLU mask matches the forward up to compute-dtype rounding).
# ---------------------------------------------------------------------------

@f32_matmuls
def _dense_layer_jnp(theta4, embed, adj, base, cd):
    """relu(base + θ4 @ (embed @ adj)) with cd-cast matmul operands and
    f32 accumulation — the XLA lowering of the fused layer."""
    nbr = jnp.einsum("bkl,bln->bkn", embed.astype(cd), adj.astype(cd),
                     preferred_element_type=jnp.float32)
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(cd), nbr.astype(cd),
                    preferred_element_type=jnp.float32)
    return jax.nn.relu(base + e3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dense_layer_hw(theta4, embed, adj, base, cd):
    from ..kernels.ops import fused_s2v_layer
    return fused_s2v_layer(theta4, embed, adj, base, compute_dtype=cd)


def _dense_layer_hw_fwd(theta4, embed, adj, base, cd):
    return _dense_layer_hw(theta4, embed, adj, base, cd), \
        (theta4, embed, adj, base)


def _dense_layer_hw_bwd(cd, res, g):
    _, vjp = jax.vjp(lambda t4, e, a, b: _dense_layer_jnp(t4, e, a, b, cd),
                     *res)
    return vjp(g)


_dense_layer_hw.defvjp(_dense_layer_hw_fwd, _dense_layer_hw_bwd)


def _dense_layer_fused(theta4, embed, adj, base, cd):
    """Dispatch for one fused dense layer by :func:`s2v_layer_impl`: the
    Pallas super-kernel on TPU, the jnp composition elsewhere (XLA's
    native fusion beats the interpret-mode kernel off-TPU)."""
    _, k, nl = embed.shape
    if s2v_layer_impl("dense", k=k, n=adj.shape[2], nl=nl,
                      compute_dtype=cd) == "pallas":
        return _dense_layer_hw(theta4, embed, adj, base, cd)
    return _dense_layer_jnp(theta4, embed, adj, base, cd)


@f32_matmuls
def _agg_jnp(embed, adj, cd):
    return jnp.einsum("bkl,bln->bkn", embed.astype(cd), adj.astype(cd),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _agg_hw(embed, adj, cd):
    from ..kernels.ops import mp_aggregate
    return mp_aggregate(embed, adj, compute_dtype=cd)


def _agg_hw_fwd(embed, adj, cd):
    return _agg_hw(embed, adj, cd), (embed, adj)


def _agg_hw_bwd(cd, res, g):
    _, vjp = jax.vjp(lambda e, a: _agg_jnp(e, a, cd), *res)
    return vjp(g)


_agg_hw.defvjp(_agg_hw_fwd, _agg_hw_bwd)


def _aggregate_fused(embed, adj, cd):
    """Aggregation-only partial (sharded dense path: the psum between
    aggregate and epilogue splits the fusion at the collective)."""
    _, k, nl = embed.shape
    if s2v_layer_impl("dense", k=k, n=adj.shape[2], nl=nl,
                      compute_dtype=cd, aggregate_only=True) == "pallas":
        return _agg_hw(embed, adj, cd)
    return _agg_jnp(embed, adj, cd)


@f32_matmuls
def embed_local(
    params: S2VParams,
    adj_local: jax.Array,       # (B, Nl, N) local rows of residual adjacency
    sol_local: jax.Array,       # (B, Nl)    local slice of partial solution S
    *,
    num_layers: int,
    axis: Optional[str] = None,  # shard_map axis name ("graph"), None = 1 device
    kernel: str = "fused",       # "fused" super-kernel | "xla" reference chain
    compute: str = "f32",        # matmul operand precision: "f32" | "bf16"
) -> jax.Array:
    """Returns (B, K, Nl) embeddings of the local resident nodes (Alg. 2)."""
    check_kernel(kernel)
    cd = compute_dtype(compute)
    b, nl, n = adj_local.shape
    k = params.dim

    # Line 5: embed1 = θ1 · Sᵀ  →  (K,1)×(B,1,Nl) = (B,K,Nl)
    embed1 = params.theta1[None, :, None] * sol_local[:, None, :]

    # Lines 7-8: w = ReLU(θ2 ⊗ Aᵀ) = ReLU(θ2 · deg_local);  embed2 = θ3 @ w.
    # θ2 is broadcast over nodes; the SpMatMul against Aᵀ sums each local
    # node's incident edge weights (its degree, for unweighted graphs).
    deg_local = adj_local.sum(-1)                           # (B, Nl)
    w = jax.nn.relu(params.theta2[None, :, None] * deg_local[:, None, :])
    embed2 = jnp.einsum("kj,bjn->bkn", params.theta3, w)    # (B, K, Nl)
    base = embed1 + embed2                                  # f32 residual term

    if axis is not None:
        my = lax.axis_index(axis)
    embed = jnp.zeros((b, k, nl), adj_local.dtype)          # Line 3

    for layer in range(num_layers):                         # Lines 9-15
        if kernel == "fused":
            if layer == 0:
                # embed⁰ = 0 (line 3) ⇒ the first aggregation and its psum
                # are exactly zero ⇒ layer 1 is relu(base), bit-identical.
                embed = jax.nn.relu(base)
            elif axis is None:
                embed = _dense_layer_fused(params.theta4, embed, adj_local,
                                           base, cd)
            else:
                # Sharded: fuse up to the collective, psum in f32, then the
                # (cheap, Nl-local) epilogue — keeps cross-mesh numerics
                # identical to the collective placement of the xla chain.
                nbr_partial = _aggregate_fused(embed, adj_local, cd)
                with jax.named_scope("mesh.collective"):
                    nbr_full = lax.psum(nbr_partial, axis)   # Line 12
                nbr_local = lax.dynamic_slice_in_dim(nbr_full, my * nl, nl,
                                                     axis=2)
                e3 = jnp.einsum("kj,bjn->bkn", params.theta4.astype(cd),
                                nbr_local.astype(cd),
                                preferred_element_type=jnp.float32)
                embed = jax.nn.relu(base + e3)               # Line 14
        else:
            # Reference "xla" per-op chain (semantics of record).
            # Line 11: partial neighbor sums from local rows: (B,K,Nl)@(B,Nl,N)
            nbr_partial = jnp.einsum("bkl,bln->bkn", embed, adj_local)
            if axis is not None:
                # Line 12: MPI_All_reduce of the (B, K, N) buffer
                with jax.named_scope("mesh.collective"):
                    nbr_full = lax.psum(nbr_partial, axis)
                nbr_local = lax.dynamic_slice_in_dim(nbr_full, my * nl, nl,
                                                     axis=2)
            else:
                nbr_local = nbr_partial                      # Nl == N
            embed3 = jnp.einsum("kj,bjn->bkn", params.theta4, nbr_local)
            embed = jax.nn.relu(base + embed3)               # Line 14
    return embed


def embed_full(params: S2VParams, adj: jax.Array, sol: jax.Array,
               *, num_layers: int, kernel: str = "fused",
               compute: str = "f32") -> jax.Array:
    """Single-device reference (Nl == N)."""
    return embed_local(params, adj, sol, num_layers=num_layers, axis=None,
                       kernel=kernel, compute=compute)
