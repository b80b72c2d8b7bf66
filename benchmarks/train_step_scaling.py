"""Training-engine scaling: host loop vs fused device-resident step.

Measures wall time per RL training step (one act→step→remember→τ×GD cycle,
paper Alg. 5) for the two engines of DESIGN.md §8 at τ ∈ {1, 4} and
P ∈ {1, 2} spatial devices.  The host loop pays 3+τ host↔device round
trips per step; the fused jitted step pays one — the gap is the point of
the device-resident engine.  Both P=1 and P=2 run in host-CPU
subprocesses (P=2 with ``--xla_force_host_platform_device_count=2``, the
mechanism of the spatial equivalence tests), and each grid records the
platform it ran on; the P=2 grid measures collective/partitioning
overhead, not real scaling.

Each grid also records the fused step's compiled-memory footprint with
and without ``donate_argnums`` (DESIGN.md §10): XLA's memory_analysis
gives argument/output/temp/aliased bytes, and resident = argument +
output + temp − aliased.  With donation the engine carry (replay ring,
params, opt) and the episode state alias their outputs, so the resident
bytes drop by roughly one copy of the donated carry — the
``donation_saved_bytes`` column.

JSON → experiments/bench/train_step_scaling.json with per-config seconds
per step and the fused-over-host speedup.

  PYTHONPATH=src python -m benchmarks.train_step_scaling [--quick]
"""
from __future__ import annotations

import argparse
import json
import time

from .common import cpu_child, platform, save

TAUS = (1, 4)


def _measure_engine(engine: str, tau: int, *, n: int, graphs: int,
                    steps: int, warm: int, spatial: int = 0) -> float:
    """Steady-state seconds per RL training step (warm replay, compiled).

    Drives each engine's per-step primitive directly — the fused jitted
    step with its single (loss, done) fetch, or the host
    act/remember/train cycle — resetting the episode state on done, so
    the timed region is exactly the recurring per-step work.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import Agent, PolicyConfig, get_rep, mesh_from_spec
    from repro.core import env as env_lib
    from repro.core.engine import engine_init, get_train_step
    from repro.core.graphs import random_graph_batch
    from repro.core.mesh import shard_batch

    adj = random_graph_batch("er", n, graphs, seed=0, rho=0.2)
    cfg = PolicyConfig(embed_dim=16, num_layers=2, minibatch=32,
                       replay_capacity=4096, learning_rate=1e-3,
                       eps_decay_steps=200, spatial=spatial)
    agent = Agent(cfg, num_nodes=n)
    rep = get_rep(cfg.graph_rep)
    source = rep.prepare_dataset(adj)
    step_fn = env_lib.make("mvc")
    residual = env_lib.residual_semantics("mvc")
    mesh = mesh_from_spec(spatial)
    b = 2                                  # graphs stepped together
    gi = np.arange(b) % graphs
    gi_dev = jnp.asarray(gi, jnp.int32)
    zeros = np.zeros((b, n), np.float32)

    def reset():
        st = rep.state_from_tuples(source, gi, zeros, residual=residual)
        return shard_batch(mesh, st) if mesh is not None else st

    state = reset()
    if engine == "device":
        fused = get_train_step(cfg, rep=rep, tau=tau,
                               target_mode=agent.target_mode)
        es = engine_init(cfg, agent.params, agent.opt, n, seed=0, mesh=mesh)

        def one_step():
            nonlocal es, state
            es, state, _a, _r, done, loss = fused(es, state, source, gi_dev)
            _loss, done = jax.device_get((loss, done))
            if done.all():
                state = reset()
    else:
        def one_step():
            nonlocal state
            action = agent.act(state, explore=True)
            new_state, reward, done = step_fn(state, jnp.asarray(action))
            agent.remember(gi, state, action, np.asarray(reward), new_state,
                           np.asarray(done))
            agent.train(source, tau=tau, residual=residual)
            state = new_state
            if bool(np.asarray(done).all()):
                state = reset()

    for _ in range(warm):                  # fill replay + compile
        one_step()
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    return (time.perf_counter() - t0) / steps


def _measure_donation(tau: int, *, n: int, graphs: int,
                      spatial: int = 0) -> dict:
    """Resident-state bytes of the compiled fused step with vs without
    ``donate_argnums`` (DESIGN.md §10 donation contract).  Donated
    buffers alias their outputs, so resident = argument + output + temp
    − aliased; the undonated build keeps caller copies of the engine
    carry and episode state live across the call."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import Agent, PolicyConfig, get_rep, mesh_from_spec
    from repro.core import env as env_lib
    from repro.core.engine import engine_init, get_train_step
    from repro.core.graphs import random_graph_batch
    from repro.core.mesh import shard_batch

    adj = random_graph_batch("er", n, graphs, seed=0, rho=0.2)
    cfg = PolicyConfig(embed_dim=16, num_layers=2, minibatch=32,
                       replay_capacity=4096, learning_rate=1e-3,
                       eps_decay_steps=200, spatial=spatial)
    agent = Agent(cfg, num_nodes=n)
    rep = get_rep(cfg.graph_rep)
    source = rep.prepare_dataset(adj)
    residual = env_lib.residual_semantics("mvc")
    mesh = mesh_from_spec(spatial)
    b = 2
    gi = np.arange(b) % graphs
    gi_dev = jnp.asarray(gi, jnp.int32)
    state = rep.state_from_tuples(source, gi, np.zeros((b, n), np.float32),
                                  residual=residual)
    if mesh is not None:
        state = shard_batch(mesh, state)
    out = {}
    for donate in (True, False):
        fused = get_train_step(cfg, rep=rep, tau=tau,
                               target_mode=agent.target_mode, donate=donate)
        es = engine_init(cfg, agent.params, agent.opt, n, seed=0, mesh=mesh)
        try:
            ma = fused.lower(es, state, source,
                             gi_dev).compile().memory_analysis()
        except Exception:                  # backend without memory stats
            return {}
        stats = {"argument_bytes": int(ma.argument_size_in_bytes),
                 "output_bytes": int(ma.output_size_in_bytes),
                 "temp_bytes": int(ma.temp_size_in_bytes),
                 "alias_bytes": int(ma.alias_size_in_bytes)}
        stats["resident_bytes"] = (stats["argument_bytes"]
                                   + stats["output_bytes"]
                                   + stats["temp_bytes"]
                                   - stats["alias_bytes"])
        out["donated" if donate else "undonated"] = stats
    out["saved_bytes"] = (out["undonated"]["resident_bytes"]
                          - out["donated"]["resident_bytes"])
    return out


def _measure_grid(n: int, graphs: int, steps: int, warm: int,
                  spatial: int) -> dict:
    out = platform()
    for tau in TAUS:
        host = _measure_engine("host", tau, n=n, graphs=graphs, steps=steps,
                               warm=warm, spatial=spatial)
        fused = _measure_engine("device", tau, n=n, graphs=graphs,
                                steps=steps, warm=warm, spatial=spatial)
        out[f"tau{tau}"] = {"host_s_per_step": host,
                            "fused_s_per_step": fused,
                            "speedup": host / fused}
    out["donation"] = _measure_donation(TAUS[0], n=n, graphs=graphs,
                                        spatial=spatial)
    return out


def run(quick: bool = False):
    n, graphs = (24, 4) if quick else (48, 8)
    steps, warm = (20, 36) if quick else (60, 40)

    results = {"config": {"n": n, "graphs": graphs, "steps": steps,
                          "minibatch": 32, "embed_dim": 16, "taus": TAUS,
                          "quick": quick}}
    for pname, spatial in (("p1", 0), ("p2", 2)):
        results[pname] = cpu_child(
            "train_step_scaling", {"n": n, "graphs": graphs, "steps": steps,
                                   "warm": warm, "spatial": spatial},
            devices=max(spatial, 1))

    save("train_step_scaling", results, quick=quick)
    rows = []
    for pname in ("p1", "p2"):
        grid = results[pname]
        if "error" in grid:
            rows.append((f"train_step_{pname}", float("nan"),
                         f"{pname} subprocess failed"))
            continue
        for tau in TAUS:
            r = grid[f"tau{tau}"]
            rows.append((
                f"train_step_{pname}_tau{tau}",
                r["fused_s_per_step"] * 1e6,
                f"host {r['host_s_per_step']*1e3:.1f}ms/step fused "
                f"{r['fused_s_per_step']*1e3:.1f}ms/step "
                f"speedup {r['speedup']:.2f}x on {grid['platform']}"))
        don = grid.get("donation") or {}
        if don:
            rows.append((
                f"train_step_{pname}_donation",
                don["saved_bytes"],
                f"resident donated "
                f"{don['donated']['resident_bytes']/1024:.1f}KiB "
                f"undonated "
                f"{don['undonated']['resident_bytes']/1024:.1f}KiB "
                f"saved {don['saved_bytes']/1024:.1f}KiB "
                f"(aliased {don['donated']['alias_bytes']/1024:.1f}KiB)"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        spec = json.loads(args.child)
        print(json.dumps(_measure_grid(spec["n"], spec["graphs"],
                                       spec["steps"], spec["warm"],
                                       spec["spatial"])))
        return
    for name, us, derived in run(quick=args.quick):
        print(f'{name},{us:.1f},"{derived}"')


if __name__ == "__main__":
    main()
