"""2-D ``(data, graph)`` device mesh and the single partitioning layer
every multi-device consumer dispatches through (DESIGN.md §10).

The paper's scaling story composes two orthogonal axes:

- **graph-level batch parallelism** (``data`` axis): B graphs — episodes,
  replay minibatches, solve/serving batches — split dp ways, B/dp graphs
  per device;
- **node-level spatial parallelism** (``graph`` axis, paper §4.1): one
  graph's N node rows split sp ways, N/sp resident rows per device, with
  the per-layer collectives of Alg. 2-4.

``make_mesh(dp, sp)`` builds the mesh; the PartitionSpec builders below
are the ONE place that knows how each array of either GraphRep state (and
the device replay buffer) lays out on it — batch dim sharded over
``data``, node rows over ``graph``, everything else replicated:

| array | dense | sparse |
|---|---|---|
| adjacency / neighbor lists | ``adj (B,N,N) → P(data, graph, None)`` | ``neighbors/valid (B,N,D) → P(data, graph, None)`` |
| solution / candidate (B, N) | ``P(data, graph)`` | ``P(data, graph)`` |
| scores out of a spatial eval | ``P(data)`` (replicated over ``graph`` post all-gather) | same |
| replay tuples (R, ·) | rows over ``data``, S masks ``P(data, graph)`` | same |
| fused solve carry | ``adj P(data, graph, None)``, masks ``P(data)`` (``DENSE_SOLVE_SPECS``) | every array ``P(data)`` |

Back-compat rule: ``PolicyConfig.spatial`` historically was an int P
meaning "P-way node sharding".  ``normalize_spatial`` keeps that contract
— ``P`` ⇒ ``(1, P)``, ``0``/``None`` ⇒ ``(1, 1)`` (no mesh) — while a
``(dp, sp)`` tuple selects the full 2-D mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

DATA = "data"     # graph-level batch parallelism (B → B/dp per device)
GRAPH = "graph"   # node-level spatial parallelism (N → N/sp per device)

MeshSpec = Union[None, int, Tuple[int, int]]


def normalize_spatial(spec: MeshSpec) -> Tuple[int, int]:
    """``PolicyConfig.spatial`` value → ``(dp, sp)`` mesh shape.

    Back-compat: an int P means the legacy 1-D node sharding ``(1, P)``;
    ``0``/``None`` mean ``(1, 1)`` (single device, no mesh)."""
    if spec is None:
        return (1, 1)
    if isinstance(spec, (tuple, list)):
        if len(spec) != 2:
            raise ValueError(f"mesh spec must be (dp, sp), got {spec!r}")
        dp, sp = int(spec[0]), int(spec[1])
        if dp < 1 or sp < 1:
            raise ValueError(f"mesh spec components must be >= 1, "
                             f"got {spec!r}")
        return (dp, sp)
    p = int(spec)
    if p < 0:
        raise ValueError(f"legacy spatial spec must be >= 0, got {spec!r}")
    return (1, 1) if p == 0 else (1, p)


def is_multi(spec: MeshSpec) -> bool:
    """True when the spec selects any multi-device partitioning."""
    return normalize_spatial(spec) != (1, 1)


def parse_spatial(text: str) -> MeshSpec:
    """CLI form → spec: ``"4"`` (legacy node sharding) or ``"dp,sp"``."""
    text = text.strip()
    if "," in text:
        dp, sp = (int(t) for t in text.split(","))
        return (dp, sp)
    return int(text)


@functools.lru_cache(maxsize=32)
def make_mesh(dp: int = 1, sp: Optional[int] = None) -> jax.sharding.Mesh:
    """The 2-D ``(data, graph)`` mesh over dp·sp devices.

    ``sp=None`` spreads the remaining devices over the ``graph`` axis
    (the legacy ``make_graph_mesh`` behaviour at dp=1)."""
    from ..sharding.compat import auto_axis_types_kw
    devs = jax.devices()
    if sp is None:
        sp = max(len(devs) // max(dp, 1), 1)
    if dp * sp > len(devs):
        raise ValueError(
            f"mesh ({dp}, {sp}) needs {dp * sp} devices, have {len(devs)} "
            f"(force more with XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={dp * sp})")
    return jax.make_mesh((dp, sp), (DATA, GRAPH), **auto_axis_types_kw(2))


def mesh_from_spec(spec: MeshSpec) -> Optional[jax.sharding.Mesh]:
    """Spec → mesh, or None when the spec is single-device ``(1, 1)``."""
    dp, sp = normalize_spatial(spec)
    return None if (dp, sp) == (1, 1) else make_mesh(dp, sp)


def mesh_shape(mesh: jax.sharding.Mesh) -> Tuple[int, int]:
    """(dp, sp) of a 2-D mesh built by :func:`make_mesh`."""
    return (mesh.shape[DATA], mesh.shape[GRAPH])


# ---------------------------------------------------------------------------
# Cross-shard communication strategy of the mesh GD step (DESIGN.md §10).
# ---------------------------------------------------------------------------

# "manual": hand-written lax.all_gather/psum/psum_scatter over per-device
# (B/dp, N/sp, ·) tiles — no operand is ever replicated.  "gspmd": the
# reference path, where GSPMD partitions the assembled minibatch onto the
# shard_map tiling.  "auto" resolves per mesh shape.
COLLECTIVES_MODES = ("auto", "manual", "gspmd")


def check_collectives(mode: str) -> str:
    if mode not in COLLECTIVES_MODES:
        raise ValueError(f"collectives must be one of {COLLECTIVES_MODES}, "
                         f"got {mode!r}")
    return mode


def resolve_collectives(mode: str, dp: int, sp: int) -> str:
    """Config value → concrete strategy for a (dp, sp) mesh.  ``auto``
    picks manual on full 2-D meshes and gspmd on 1-D meshes."""
    check_collectives(mode)
    if mode != "auto":
        return mode
    return "manual" if (dp > 1 and sp > 1) else "gspmd"


# ---------------------------------------------------------------------------
# PartitionSpec builders: the unified in/out specs for both GraphRep states.
# ---------------------------------------------------------------------------

# scores / per-tuple arrays: batch over `data`, replicated over `graph`
SCORES_SPEC = P(DATA)
TUPLE_SPEC = P(DATA)

_DENSE_FIELD_SPECS = {"adj": P(DATA, GRAPH, None),
                      "candidate": P(DATA, GRAPH),
                      "solution": P(DATA, GRAPH)}
_SPARSE_FIELD_SPECS = {"neighbors": P(DATA, GRAPH, None),
                       "valid": P(DATA, GRAPH, None),
                       "candidate": P(DATA, GRAPH),
                       "solution": P(DATA, GRAPH)}
# CSR rows are ragged, so edge arrays cannot split over `graph` (unequal
# per-device edge counts) — csr shards the BATCH dim only; sp > 1 is
# rejected up front by engine._check_csr_spatial.
_CSR_FIELD_SPECS = {"indptr": P(DATA),
                    "indices": P(DATA),
                    "edge_mask": P(DATA),
                    "candidate": P(DATA),
                    "solution": P(DATA)}

# positional shard_map in_spec tuples, derived from the field tables above
# (the single source of truth) — callers prepend the replicated P() spec
# for params when building in_specs
# (adj, solution, candidate) of the dense state:
DENSE_STATE_SPECS = tuple(_DENSE_FIELD_SPECS[k]
                          for k in ("adj", "solution", "candidate"))
# (neighbors, valid, solution, candidate) of the sparse state:
SPARSE_STATE_SPECS = tuple(_SPARSE_FIELD_SPECS[k]
                           for k in ("neighbors", "valid", "solution",
                                     "candidate"))
_REPLAY_FIELD_SPECS = {"graph_idx": P(DATA), "solution": P(DATA, GRAPH),
                       "action": P(DATA), "target": P(DATA),
                       "reward": P(DATA), "next_solution": P(DATA, GRAPH),
                       "done": P(DATA), "size": P(), "ptr": P()}


def state_field_specs(state) -> dict:
    """Field-name → PartitionSpec for a GraphRep state (dense, sparse or
    csr)."""
    from .graphs import CsrGraphState, SparseGraphState
    if isinstance(state, CsrGraphState):
        return _CSR_FIELD_SPECS
    return (_SPARSE_FIELD_SPECS if isinstance(state, SparseGraphState)
            else _DENSE_FIELD_SPECS)


def _apply(mesh, obj, specs, place):
    kw = {name: place(getattr(obj, name), NamedSharding(mesh, spec))
          for name, spec in specs.items()}
    return dataclasses.replace(obj, **kw)


def shard_state(mesh, state):
    """Host-side placement of a GraphRep state onto the mesh partitioning
    (batch over ``data``, node rows over ``graph``)."""
    return _apply(mesh, state, state_field_specs(state), jax.device_put)


def shard_batch(mesh, state):
    """Host-side placement of ONLY the batch dim over ``data`` — the
    resident layout the fused engines keep episode/solve states in, so
    the state buffers they donate (``donate_argnums``) can alias outputs
    from the FIRST call instead of warning and copying.

    When the batch dim does not divide ``dp`` (``jax.device_put`` needs
    even tiles), the state is returned unplaced — GSPMD still shards it
    unevenly inside the jitted step, exactly the pre-donation behavior;
    only the first-call aliasing is lost."""
    specs = state_field_specs(state)
    dp = mesh.shape[DATA]
    for name in specs:
        arr = getattr(state, name)
        if arr is not None and arr.shape[0] % dp:
            return state
    return _apply(mesh, state, {name: P(DATA) for name in specs},
                  jax.device_put)


def constrain_batch(mesh, state):
    """Constrain ONLY the batch dim of every state array over ``data``.

    This is the layout of replicated-per-node phases (acting, the fused
    train step's bookkeeping): per-graph rows stay whole so their
    arithmetic is bit-identical to the single-device path, while the batch
    splits dp ways; the node axis is tiled over ``graph`` only inside the
    spatial ``shard_map`` evaluations."""
    specs = {name: P(DATA) for name in state_field_specs(state)}
    return _apply(mesh, state, specs, jax.lax.with_sharding_constraint)


# The fused solve's carry on a mesh (paper §5.3 distributed storage): a
# dense state keeps its adjacency rows over `graph` for the whole solve,
# N/sp resident rows per device, with its (B, N) masks whole on every
# device of a graph group; the other reps' states split by batch only.
DENSE_SOLVE_SPECS = {"adj": P(DATA, GRAPH, None), "candidate": P(DATA),
                     "solution": P(DATA)}


def solve_state_specs(state) -> dict:
    """Field-name → PartitionSpec of a state in the fused solve's carry."""
    from .graphs import GraphState
    if isinstance(state, GraphState):
        return DENSE_SOLVE_SPECS
    return {name: P(DATA) for name in state_field_specs(state)}


def shard_rows(mesh, adj):
    """A dense (B, N, N) or (N, N) adjacency laid out as the solve carry
    holds it: rows over ``graph``, batch over ``data``.  An array already
    so laid out is returned as it is; any other is placed by rows, so no
    device receives rows that are not its own."""
    sharding = NamedSharding(mesh, DENSE_SOLVE_SPECS["adj"])
    if adj.ndim == 2:
        adj = adj[None]
    dp, sp = mesh_shape(mesh)
    if adj.shape[0] % dp or adj.shape[1] % sp:
        raise ValueError(f"dense adjacency {tuple(adj.shape)}: batch not "
                         f"divisible by the data-axis size {dp} or rows "
                         f"by the graph-axis size {sp}")
    if isinstance(adj, jax.Array) and adj.sharding.is_equivalent_to(
            sharding, adj.ndim):
        return adj
    return jax.device_put(adj, sharding)


def shard_solve_state(mesh, state):
    """Host-side placement of a fresh solve state in the carry's layout
    (:func:`solve_state_specs`), so the state buffers the fused solve
    donates alias its carry from the first call.  A dense adjacency is
    placed with :func:`shard_rows`; the other reps as :func:`shard_batch`
    places them."""
    from .graphs import GraphState
    if not isinstance(state, GraphState):
        return shard_batch(mesh, state)
    masks = {k: v for k, v in DENSE_SOLVE_SPECS.items() if k != "adj"}
    state = _apply(mesh, state, masks, jax.device_put)
    return dataclasses.replace(state, adj=shard_rows(mesh, state.adj))


def constrain_solve_state(mesh, state):
    """jit-traceable ``with_sharding_constraint`` version of
    :func:`shard_solve_state`: the fused solve pins its carry with it."""
    return _apply(mesh, state, solve_state_specs(state),
                  jax.lax.with_sharding_constraint)


def shard_replay(mesh, replay):
    """Host-side placement of a DeviceReplay: tuple rows over ``data``,
    the O(N) solution masks additionally over ``graph`` — per-device
    replay storage 8·R·(N/sp + 1)/dp bytes (§5.2 generalized)."""
    return _apply(mesh, replay, _REPLAY_FIELD_SPECS, jax.device_put)


def constrain_replay(mesh, replay):
    """jit-traceable ``with_sharding_constraint`` version of
    :func:`shard_replay`."""
    return _apply(mesh, replay, _REPLAY_FIELD_SPECS,
                  jax.lax.with_sharding_constraint)


# training dataset sources (dense (G, N, N) stack / SparseGraphBatch):
# graphs replicated, node rows over `graph` — the manual-collective GD
# step gathers its (M/dp, N/sp, ·) minibatch tiles from these resident
# row blocks, so their layout must match the shard_map in_specs exactly.
DATASET_SPEC = P(None, GRAPH, None)


def constrain_dataset(mesh, source):
    """Constrain a training dataset source's node rows over ``graph``
    (manual-collective path).  ``source`` is the dense (G, N, N) stack or
    anything carrying ``neighbors``/``valid`` (a SparseGraphBatch)."""
    ns = NamedSharding(mesh, DATASET_SPEC)
    csc = jax.lax.with_sharding_constraint
    if isinstance(source, jax.Array):
        return csc(source, ns)
    return dataclasses.replace(source, neighbors=csc(source.neighbors, ns),
                               valid=csc(source.valid, ns))


# ---------------------------------------------------------------------------
# §5.2 memory model generalized to the 2-D mesh: batch divided by dp, node
# rows by sp, replay tuples by dp with O(N/sp) masks per tuple.
# ---------------------------------------------------------------------------

def per_device_bytes(n: int, b: int, rho: float, p: int,
                     replay_tuples: int = 0, dp: int = 1) -> dict:
    """Paper §5.2 memory model, per device, on the (dp, sp=p) mesh:
    sparse-COO adjacency 20·N²·ρ·B/(dp·sp) bytes, masks 4·N·B/(dp·sp)
    each, replay 8·R·(N/sp + 1)/dp."""
    return {
        "adjacency": 20.0 * n * n * rho * b / (p * dp),
        "solution": 4.0 * n * b / (p * dp),
        "candidates": 4.0 * n * b / (p * dp),
        "replay": 8.0 * replay_tuples * (n / p + 1) / dp,
    }


def sparse_per_device_bytes(n: int, max_deg: int, b: int, p: int,
                            replay_tuples: int = 0, dp: int = 1) -> dict:
    """Padded edge-list storage per device on the (dp, sp=p) mesh (this
    repo's TPU adaptation of §5.2): 4-byte neighbor ids + 1-byte validity
    per slot, masks as above."""
    return {
        "adjacency": 5.0 * n * max_deg * b / (p * dp),
        "solution": 4.0 * n * b / (p * dp),
        "candidates": 4.0 * n * b / (p * dp),
        "replay": 8.0 * replay_tuples * (n / p + 1) / dp,
    }


def minibatch_operand_bytes(n: int, minibatch: int, dp: int, sp: int,
                            rep: str = "dense",
                            max_deg: Optional[int] = None) -> dict:
    """Per-device LIVE bytes of the GD loss operands inside one spatial GD
    iteration (§5.2): every operand is a tile — topology (M/dp, N/sp, ·),
    solution/candidate (M/dp, N/sp), action/target (M/dp,) — so bytes
    fall ~1/(dp·sp) with the mesh under either collectives strategy."""
    if rep == "dense":
        topo = 4.0 * minibatch * n * n / (dp * sp)
    else:
        d = max_deg if max_deg else n
        topo = 5.0 * minibatch * n * d / (dp * sp)
    out = {
        "topology": topo,
        "solution": 4.0 * minibatch * n / (dp * sp),
        "candidate": 4.0 * minibatch * n / (dp * sp),
        "tuples": 2 * 4.0 * minibatch / dp,         # action + target
    }
    out["total"] = sum(out.values())
    return out


def csr_per_device_bytes(n: int, edges: int, b: int,
                         replay_tuples: int = 0, dp: int = 1) -> dict:
    """Flat CSR storage per device (DESIGN.md §13) — the EDGE-proportional
    cost formula: 4-byte column ids + 1-byte mask per directed edge slot
    plus the 4·(N+1) row pointers; no N² and no N·maxdeg term.  CSR shards
    the batch only (sp ≡ 1), so everything divides by dp alone."""
    return {
        "adjacency": (5.0 * edges + 4.0 * (n + 1)) * b / dp,
        "solution": 4.0 * n * b / dp,
        "candidates": 4.0 * n * b / dp,
        "replay": 8.0 * replay_tuples * (n + 1) / dp,
    }
