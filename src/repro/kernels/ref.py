"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: kernel tests sweep shapes/dtypes and
assert_allclose against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# structure2vec message passing (paper Alg. 2) — the per-device hot loop.
# ---------------------------------------------------------------------------

def mp_aggregate(embed: jax.Array, adj: jax.Array) -> jax.Array:
    """nbr[b,k,n] = Σ_l embed[b,k,l] · adj[b,l,n]  (Alg. 2 line 11)."""
    return jnp.einsum("bkl,bln->bkn", embed.astype(jnp.float32),
                      adj.astype(jnp.float32))


def s2v_layer(theta4, embed, adj, base) -> jax.Array:
    """One full dense embedding layer (Alg. 2 lines 11+13-14 fused):
    relu(base + θ4 @ (embed @ adj))."""
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(jnp.float32),
                    mp_aggregate(embed, adj))
    return jax.nn.relu(base.astype(jnp.float32) + e3)


def sparse_mp_aggregate(x: jax.Array, neighbors: jax.Array,
                        edge: jax.Array) -> jax.Array:
    """Sparse (padded edge-list) neighbor aggregation:
    nbr_sum[b,k,i] = Σ_d x[b,k,neighbors[b,i,d]] · edge[b,i,d].

    x (B, K, N+1) with a zero sentinel column; neighbors (B, N, D) int32
    padded with N; edge (B, N, D) residual-edge factors."""
    gathered = jax.vmap(lambda xb, nb: xb[:, nb])(
        x.astype(jnp.float32), neighbors)                   # (B, K, N, D)
    return jnp.einsum("bknd,bnd->bkn", gathered, edge.astype(jnp.float32))


def s2v_layer_sparse(theta4, x, neighbors, edge, base) -> jax.Array:
    """One full sparse embedding layer: relu(base + θ4 @ nbr_sum) where
    nbr_sum is the padded edge-list aggregation above.  ``x`` is (B, K, N)
    WITHOUT a sentinel column — padded ids equal N and select the zero
    column appended here (the fused kernel is sentinel-free by iota range
    instead)."""
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, 0), (0, 1)))
    nbr = sparse_mp_aggregate(xp, neighbors, edge)
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(jnp.float32), nbr)
    return jax.nn.relu(base.astype(jnp.float32) + e3)


def s2v_layer_csr(theta4, x, indices, row_ids, edge_w, base) -> jax.Array:
    """One full CSR embedding layer: relu(base + θ4 @ nbr_sum) with
    nbr_sum[b,k,n] = Σ_{e: row_ids[b,e]=n} x[b,k,indices[b,e]]·edge_w[b,e].
    ``x`` is (B, K, N) WITHOUT a sentinel column — padded slots carry id N
    and select the zero column appended here."""
    n = x.shape[-1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, 0), (0, 1)))
    gathered = jax.vmap(lambda xb, ib: xb[:, ib])(xp, indices)   # (B, K, E)
    weighted = gathered * edge_w.astype(jnp.float32)[:, None, :]
    nbr = jax.vmap(lambda wb, rb: jax.ops.segment_sum(
        wb.T, rb, num_segments=n).T)(weighted, row_ids)
    e3 = jnp.einsum("kj,bjn->bkn", theta4.astype(jnp.float32), nbr)
    return jax.nn.relu(base.astype(jnp.float32) + e3)


# ---------------------------------------------------------------------------
# WKV6: RWKV-6 ("Finch") linear-attention recurrence with data-dependent
# per-channel decay.  Shapes: r/k/w (BH, T, dk), v (BH, T, dv), u (BH, dk).
# w is the *decay multiplier* in (0, 1].
# ---------------------------------------------------------------------------

def wkv6(r, k, v, w, u, s0=None):
    """Sequential scan oracle.

    o_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t);  S_t = diag(w_t) S_{t-1} + k_tᵀ v_t
    Returns (out (BH, T, dv), final_state (BH, dk, dv)).
    """
    bh, t, dk = r.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    r, k, v, w, u = (x.astype(f32) for x in (r, k, v, w, u))
    if s0 is None:
        s0 = jnp.zeros((bh, dk, dv), f32)

    def step(s, inp):
        rt, kt, vt, wt = inp                     # (bh,dk),(bh,dk),(bh,dv),(bh,dk)
        kv = kt[:, :, None] * vt[:, None, :]     # (bh, dk, dv)
        ot = jnp.einsum("bi,bij->bj", rt, s + u[:, :, None] * kv)
        s = wt[:, :, None] * s + kv
        return s, ot

    s, out = jax.lax.scan(step, s0,
                          (r.swapaxes(0, 1), k.swapaxes(0, 1),
                           v.swapaxes(0, 1), w.swapaxes(0, 1)))
    return out.swapaxes(0, 1), s


# ---------------------------------------------------------------------------
# Sliding-window causal attention (gemma3 local layers).
# q (BH, Tq, d), k/v (BH, Tk, d); window w: query i attends keys
# j ∈ [i - w + 1, i] (causal, inclusive of self).
# ---------------------------------------------------------------------------

def swa(q, k, v, window: int, scale: float | None = None):
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qi = jnp.arange(tq)[:, None]
    kj = jnp.arange(tk)[None, :]
    mask = (kj <= qi) & (kj > qi - window)
    logits = jnp.where(mask[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Grouped expert GLU FFN (MoE hotspot): per-expert silu(x@wg)*(x@wu) @ wo.
# ---------------------------------------------------------------------------

def grouped_glu_ffn(x, wg, wu, wo):
    """x (E, C, d); wg/wu (E, d, f); wo (E, f, d) → (E, C, d) f32."""
    f32 = jnp.float32
    g = jnp.einsum("ecd,edf->ecf", x.astype(f32), wg.astype(f32))
    u = jnp.einsum("ecd,edf->ecf", x.astype(f32), wu.astype(f32))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wo.astype(f32))
