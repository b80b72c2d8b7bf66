"""2-D (data, graph) mesh scaling: fused train step and fused solve wall
time plus MEASURED per-device memory across (dp, sp) ∈ {(1,1), (2,1),
(1,2), (2,2)} at a fixed global batch (DESIGN.md §10).

Each mesh shape runs in a subprocess with a forced 4-device CPU topology
(same mechanism as the spatial equivalence tests); on this container the
wall times measure collective/partitioning overhead rather than real
scaling, but the per-device byte counts are real: the replay ring buffer
and the solve-state arrays are placed with the mesh shardings and their
addressable shard sizes recorded — peak per-device state bytes must fall
with dp at fixed global batch (the acceptance claim), and mask/neighbor
rows with sp.  The §5.2 analytic model at the same shape is saved
alongside for comparison.

Each multi-device shape times the fused train step under BOTH collective
strategies (DESIGN.md §10) — ``manual`` (hand-written lax collectives
over per-device tiles, no operand ever replicated) vs ``gspmd`` (the
GSPMD-partitioned reference path) — and records the §5.2 per-device
live-operand byte model.  Two RuntimeError guards: at (2,2) the manual
path must be no slower than gspmd (within measurement tolerance), and its
operand bytes must scale ~1/(dp·sp) against the single device.  Compiled
memory stats (temp/alias/argument bytes from XLA's memory_analysis) are
stored per strategy as the measured counterpart.  Every shape runs in a
host-CPU child and records the platform it ran on; the parent never
touches JAX.

Each mesh shape also records PER-COLLECTIVE microbench columns — the
workload's §5.1/§5.2 communication terms in isolation: the dense layer's
(B, K, N) ``psum`` over ``graph``, the sparse layer's embedding
``all_gather`` over ``graph``, the (B, N) solution-mask all-gather (the
C/S broadcast), and the ``data``-axis gradient psum at policy-parameter
size.  On the forced-CPU topology these measure dispatch/partitioning
overhead rather than interconnect bandwidth; they are committed so
shape-to-shape regressions are visible.

JSON → experiments/bench/mesh_scaling.json.

  PYTHONPATH=src python -m benchmarks.mesh_scaling [--quick]
"""
from __future__ import annotations

import argparse
import json
import time

from .common import cpu_child, platform, save

MESHES = ((1, 1), (2, 1), (1, 2), (2, 2))


def _shard_nbytes(tree) -> int:
    """Per-device bytes of a pytree of sharded jax arrays (shard 0)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "addressable_shards"):
            total += leaf.addressable_shards[0].data.nbytes
    return total


def _collective_times(mesh, params, *, n: int, b: int, k: int = 16,
                      repeat: int = 20) -> dict:
    """Isolated per-collective timings on the (dp, sp) mesh: seconds per
    call for each communication term the fused layers/train step issue.
    Axis-size-1 collectives are omitted (they lower to no-ops)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.core.mesh import DATA, GRAPH
    from repro.sharding.compat import shard_map_nocheck

    dp, sp = mesh.shape[DATA], mesh.shape[GRAPH]
    out = {}

    def bench(name, fn, in_specs, out_specs, x):
        f = jax.jit(shard_map_nocheck(fn, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs))
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(repeat):
            r = f(x)
        r.block_until_ready()
        out[name] = (time.perf_counter() - t0) / repeat

    if sp > 1:
        # dense layer line 12: all-reduce of the (B, K, N) partial sums
        bench("psum_graph_bkn_s", lambda x: lax.psum(x, GRAPH), P(), P(),
              jnp.zeros((b, k, n), jnp.float32))
        # sparse layer: all-gather of the (B, K, N/P) embedding buffer
        bench("all_gather_embed_s",
              lambda x: lax.all_gather(x, GRAPH, axis=2, tiled=True),
              P(None, None, GRAPH), P(),
              jnp.zeros((b, k, n), jnp.float32))
        # §5.1 C/S broadcast: all-gather of the (B, N/P) solution mask
        bench("all_gather_solution_s",
              lambda x: lax.all_gather(x, GRAPH, axis=1, tiled=True),
              P(None, GRAPH), P(), jnp.zeros((b, n), jnp.float32))
    if dp > 1:
        # train step: gradient all-reduce over `data` at policy-param size
        psize = int(sum(x.size for x in jax.tree.leaves(params)))
        bench("psum_data_grads_s", lambda x: lax.psum(x, DATA), P(), P(),
              jnp.zeros((psize,), jnp.float32))
    return out


def _measure_train(cfg, rep, source, mesh, *, n: int, graphs: int,
                   batch: int, steps: int, warm: int):
    """s/step + compiled memory stats of the fused train step under the
    cfg's collectives strategy; returns (s_per_step, memory, replay_bytes)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import Agent
    from repro.core.engine import engine_init, get_train_step
    from repro.core.mesh import shard_batch

    agent = Agent(cfg, num_nodes=n)
    fused = get_train_step(cfg, rep=rep, tau=1, target_mode="fresh")
    es = engine_init(cfg, agent.params, agent.opt, n, seed=0, mesh=mesh)
    gi = np.arange(batch) % graphs
    gi_dev = jnp.asarray(gi, jnp.int32)
    zeros = np.zeros((batch, n), np.float32)

    def reset():
        st = rep.state_from_tuples(source, gi, zeros)
        return shard_batch(mesh, st) if mesh is not None else st

    state = reset()
    memory = {}
    try:
        ma = fused.lower(es, state, source,
                         gi_dev).compile().memory_analysis()
        memory = {"temp_bytes": int(ma.temp_size_in_bytes),
                  "alias_bytes": int(ma.alias_size_in_bytes),
                  "argument_bytes": int(ma.argument_size_in_bytes),
                  "output_bytes": int(ma.output_size_in_bytes)}
    except Exception:                      # backend without memory stats
        pass
    for _ in range(warm):
        es, state, _a, _r, done, loss = fused(es, state, source, gi_dev)
        _l, done = jax.device_get((loss, done))
        if done.all():
            state = reset()
    t0 = time.perf_counter()
    for _ in range(steps):
        es, state, _a, _r, done, loss = fused(es, state, source, gi_dev)
        _l, done = jax.device_get((loss, done))
        if done.all():
            state = reset()
    train_s = (time.perf_counter() - t0) / steps
    return train_s, memory, _shard_nbytes(es.replay)


def _measure_mesh(dp: int, sp: int, *, n: int, graphs: int, batch: int,
                  steps: int, warm: int, solve_batch: int) -> dict:
    """Seconds per fused train step (per collectives strategy) / per fused
    solve + measured per-device bytes on the (dp, sp) mesh.  Runs inside
    the forced-device child."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core import (PolicyConfig, get_rep, init_policy,
                            mesh_from_spec, shard_state, solve)
    from repro.core.graphs import random_graph_batch
    from repro.core.mesh import (minibatch_operand_bytes, per_device_bytes,
                                 resolve_collectives)

    spec = 0 if (dp, sp) == (1, 1) else (dp, sp)
    rho = 0.2
    adj = random_graph_batch("er", n, graphs, seed=0, rho=rho)
    cfg = PolicyConfig(embed_dim=16, num_layers=2, minibatch=32,
                       replay_capacity=2048, learning_rate=1e-3,
                       eps_decay_steps=200, spatial=spec)
    rep = get_rep(cfg.graph_rep)
    source = rep.prepare_dataset(adj)
    mesh = mesh_from_spec(spec)
    params = init_policy(jax.random.key(0), cfg)

    # -- fused train step, each collective strategy -------------------------
    strategies = ("single",) if mesh is None else ("manual", "gspmd")
    default = ("single" if mesh is None
               else resolve_collectives("auto", dp, sp))
    by_coll, operand = {}, {}
    replay_dev_bytes = 0
    for strat in strategies:
        c = (cfg if strat == "single"
             else dataclasses.replace(cfg, collectives=strat))
        train_s, memory, rb = _measure_train(
            c, rep, source, mesh, n=n, graphs=graphs, batch=batch,
            steps=steps, warm=warm)
        by_coll[strat] = {"s_per_step": train_s, "memory": memory}
        operand[strat] = minibatch_operand_bytes(
            n, cfg.minibatch, dp, sp, rep=rep.name)
        if strat == default:
            replay_dev_bytes = rb
    train_s = by_coll[default]["s_per_step"]

    # -- fused solve --------------------------------------------------------
    solve_adj = random_graph_batch("er", n, solve_batch, seed=7, rho=rho)
    kw = dict(num_layers=2, multi_node=True, engine="device", spatial=spec)
    solve(params, solve_adj, **kw)                         # compile
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        res = solve(params, solve_adj, **kw)
    solve_s = (time.perf_counter() - t0) / reps

    # -- measured per-device state bytes at fixed global batch --------------
    st = rep.init_state(jnp.asarray(solve_adj))
    if mesh is not None:
        st = shard_state(mesh, st)
    state_dev_bytes = _shard_nbytes(st)
    if mesh is None:                       # single device: full arrays
        state_dev_bytes = int(sum(x.nbytes for x in jax.tree.leaves(st)))

    model = per_device_bytes(n=n, b=solve_batch, rho=rho, p=sp,
                             replay_tuples=cfg.replay_capacity, dp=dp)
    coll = {} if mesh is None else _collective_times(
        mesh, params, n=n, b=solve_batch, k=cfg.embed_dim)
    return {
        **platform(),
        "train_s_per_step": train_s,
        "train_collectives_default": default,
        "train_by_collectives": by_coll,
        "operand_bytes_per_device": operand,
        "solve_s": solve_s,
        "solve_evals": int(res.policy_evals),
        "state_bytes_per_device": int(state_dev_bytes),
        "replay_bytes_per_device": int(replay_dev_bytes),
        "model_bytes_per_device": model,
        "collectives_s_per_call": coll,
    }


def run(quick: bool = False):
    n, graphs = (24, 4) if quick else (48, 8)
    steps, warm = (12, 20) if quick else (40, 30)
    batch, solve_batch = 4, 8

    results = {"config": {"n": n, "graphs": graphs, "batch": batch,
                          "solve_batch": solve_batch, "steps": steps,
                          "minibatch": 32, "embed_dim": 16,
                          "quick": quick, "meshes": list(MESHES)}}
    for dp, sp in MESHES:
        results[f"{dp}x{sp}"] = cpu_child(
            "mesh_scaling", {"dp": dp, "sp": sp, "n": n, "graphs": graphs,
                             "batch": batch, "steps": steps, "warm": warm,
                             "solve_batch": solve_batch}, devices=4)

    save("mesh_scaling", results, quick=quick)
    failed = [f"{dp}x{sp}" for dp, sp in MESHES
              if "error" in results[f"{dp}x{sp}"]]
    if failed:
        # JSON (incl. stderr tails) is already on disk for debugging;
        # fail loudly so bench-smoke CI can't go green on a broken mesh.
        raise RuntimeError(
            f"mesh shapes {failed} failed — see "
            f"experiments/bench/mesh_scaling.json: "
            + " | ".join(results[k]["error"][-200:] for k in failed))
    # -- guards (DESIGN.md §10) on the full 2-D mesh.
    r22 = results["2x2"]["train_by_collectives"]
    man_s, gsp_s = (r22["manual"]["s_per_step"],
                    r22["gspmd"]["s_per_step"])
    if man_s > gsp_s * 1.10:               # 10% CPU-timer noise allowance
        raise RuntimeError(
            f"manual collectives slower than gspmd at (2,2): "
            f"{man_s*1e3:.1f}ms vs {gsp_s*1e3:.1f}ms per step")
    # manual keeps (B/dp, N/sp) tiles — §5.2 says ~1/(dp·sp) of the single
    # device's live operands; allow slack for the unscaled tuple scalars
    # (actions/targets shard only by dp).
    man_b = results["2x2"]["operand_bytes_per_device"]["manual"]["total"]
    one_b = results["1x1"]["operand_bytes_per_device"]["single"]["total"]
    if man_b > one_b * (1.4 / 4):
        raise RuntimeError(
            f"manual per-device operand bytes did not scale ~1/(dp*sp) "
            f"at (2,2): manual {man_b} vs one device {one_b}")
    rows = []
    for dp, sp in MESHES:
        r = results[f"{dp}x{sp}"]
        rows.append((
            f"mesh_{dp}x{sp}",
            r["train_s_per_step"] * 1e6,
            f"{r['platform']}: train {r['train_s_per_step']*1e3:.1f}ms/step "
            f"solve "
            f"{r['solve_s']*1e3:.1f}ms state/dev "
            f"{r['state_bytes_per_device']/1024:.1f}KiB replay/dev "
            f"{r['replay_bytes_per_device']/1024:.1f}KiB"))
        bc = r.get("train_by_collectives") or {}
        if "manual" in bc:
            ob = r["operand_bytes_per_device"]
            rows.append((
                f"mesh_{dp}x{sp}_strategies",
                bc["manual"]["s_per_step"] * 1e6,
                f"manual {bc['manual']['s_per_step']*1e3:.1f}ms/step "
                f"gspmd {bc['gspmd']['s_per_step']*1e3:.1f}ms/step "
                f"operand/dev {ob['manual']['total']/1024:.1f}KiB"))
        coll = r.get("collectives_s_per_call") or {}
        if coll:
            rows.append((
                f"mesh_{dp}x{sp}_collectives",
                min(coll.values()) * 1e6,
                " ".join(f"{name[:-2]} {s*1e6:.0f}us"
                         for name, s in sorted(coll.items()))))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        spec = json.loads(args.child)
        print(json.dumps(_measure_mesh(
            spec["dp"], spec["sp"], n=spec["n"], graphs=spec["graphs"],
            batch=spec["batch"], steps=spec["steps"], warm=spec["warm"],
            solve_batch=spec["solve_batch"])))
        return
    for name, us, derived in run(quick=args.quick):
        print(f'{name},{us:.1f},"{derived}"')


if __name__ == "__main__":
    main()
