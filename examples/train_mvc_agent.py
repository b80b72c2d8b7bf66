"""End-to-end driver (the paper's kind: RL training).

Trains the OpenGraphGym-MG agent on any registered graph problem — mvc
(default), maxcut, mis, mds — for a few hundred RL steps with the paper's
algorithmic settings (Alg. 5 + §4.5 optimizations), evaluating solution
quality every ``--eval-every`` steps, and reports the learning curve +
final comparison vs the problem's classical baselines.

    PYTHONPATH=src python examples/train_mvc_agent.py --steps 400 --nodes 30
    PYTHONPATH=src python examples/train_mvc_agent.py --problem mds
"""
import argparse

import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.core import (Agent, PolicyConfig, train_agent, evaluate_quality,
                        parse_spatial, solve)
from repro.core import env as env_lib
from repro.core.graphs import random_graph_batch
from repro.core.solvers import (heuristic_batch, matching_2approx_batch,
                                reference_sizes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--nodes", type=int, default=25)
    ap.add_argument("--graphs", type=int, default=8)
    ap.add_argument("--kind", choices=["er", "ba", "social"], default="er")
    ap.add_argument("--problem", default="mvc",
                    choices=["mvc", "maxcut", "mis", "mds"],
                    help="registered environment to train on: mvc (min "
                         "vertex cover), maxcut (max cut), mis (max "
                         "independent set), mds (min dominating set)")
    ap.add_argument("--tau", type=int, default=4,
                    help="GD iterations per env step (paper §4.5.2)")
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--embed-dim", type=int, default=32)
    ap.add_argument("--rep", choices=["dense", "sparse", "csr"], default="dense",
                    help="GraphRep backend (DESIGN.md §1): sparse stores "
                         "O(N·maxdeg) padded edge lists instead of O(N²)")
    ap.add_argument("--engine", choices=["device", "host"], default="device",
                    help="training engine (DESIGN.md §8): 'device' fuses "
                         "act→step→remember→τ×GD into one jitted call")
    ap.add_argument("--spatial", default="0",
                    help="2-D (data, graph) mesh spec (DESIGN.md §10): "
                         "'dp,sp' shards episode/minibatch rows dp ways "
                         "over the data axis and node rows sp ways over "
                         "the graph axis (paper Alg. 5 generalized); a "
                         "bare int P means the legacy node sharding "
                         "(1, P); 0 → single device")
    ap.add_argument("--collectives", default="auto",
                    choices=["auto", "manual", "gspmd"],
                    help="cross-shard strategy for the fused train step "
                         "(DESIGN.md §10): 'manual' = hand-written lax "
                         "collectives over per-device tiles (no operand "
                         "replication), 'gspmd' = compiler-partitioned "
                         "reference path; 'auto' picks manual on full 2-D "
                         "meshes (dp>1 and sp>1) and gspmd elsewhere")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save the trained policy params here "
                         "(repro.checkpoint format; load with "
                         "`python -m repro.launch.solve_serve --ckpt-dir` "
                         "or GraphSolverService.from_checkpoint)")
    args = ap.parse_args()
    setup_compile_cache()

    kw = {"er": {"rho": 0.15}, "ba": {"d": 4}, "social": {}}[args.kind]
    train = random_graph_batch(args.kind, args.nodes, args.graphs, seed=0,
                               **kw)
    test = random_graph_batch(args.kind, args.nodes, 8, seed=777, **kw)
    # references: exact/LB only exists for MVC; the other problems use
    # their matching greedy heuristic as the quality yardstick.  MaxCut is
    # scored by CUT VALUE along the commit trajectory, not |S| — the env
    # eventually assigns every positive-degree node, so the final set
    # size says nothing about quality.
    if args.problem == "mvc":
        refs = reference_sizes(test)
    elif args.problem == "maxcut":
        import jax.numpy as jnp
        from repro.core.env import cut_value
        refs = np.asarray(cut_value(jnp.asarray(test), jnp.asarray(
            heuristic_batch("maxcut", test), jnp.float32)))
    else:
        refs = heuristic_batch(args.problem, test).sum(-1)

    cfg = PolicyConfig(embed_dim=args.embed_dim, num_layers=2, minibatch=64,
                       replay_capacity=10_000, learning_rate=args.lr,
                       eps_decay_steps=args.steps // 2, graph_rep=args.rep,
                       engine=args.engine,
                       spatial=parse_spatial(args.spatial),
                       collectives=args.collectives)
    agent = Agent(cfg, num_nodes=args.nodes)

    curve = []

    def ev(ag):
        if args.problem == "maxcut":
            from repro.core.inference import best_trajectory_cut
            cuts = best_trajectory_cut(ag.params, test,
                                       num_layers=ag.cfg.num_layers)
            r = float(np.mean(cuts / np.maximum(refs, 1)))
        else:
            r = evaluate_quality(ag, test, refs,  # rep follows graph_rep
                                 problem=args.problem)
        curve.append((ag.step_count, r))
        better = "higher" if env_lib.sense(args.problem) == "max" else "lower"
        print(f"  step {ag.step_count:5d}  ratio-vs-ref {r:.3f} "
              f"({better} is better)")
        return r

    print(f"training {args.problem} on {args.graphs} "
          f"{args.kind}({args.nodes}) graphs, tau={args.tau} ...")
    log = train_agent(agent, train, problem=args.problem,
                      episodes=10 ** 6, tau=args.tau,
                      eval_every=args.eval_every, eval_fn=ev,
                      max_steps=args.steps, seed=1)
    print(f"done in {log.wall_time:.1f}s; final loss "
          f"{log.losses[-1]:.4f}")

    if args.ckpt_dir:
        from repro.checkpoint import save_policy
        path = save_policy(args.ckpt_dir, agent.step_count, agent.params)
        print(f"policy params saved to {path}")

    name = args.problem.upper()
    if args.problem == "maxcut":
        from repro.core.inference import best_trajectory_cut
        cuts = best_trajectory_cut(agent.params, test,
                                   num_layers=cfg.num_layers)
        print(f"RL best-trajectory cut   : {cuts.mean():.2f}")
        print(f"greedy cut               : {refs.mean():.2f}")
    else:
        res = solve(agent.params, test, num_layers=cfg.num_layers,
                    multi_node=True, rep=args.rep, problem=args.problem)
        print(f"RL (adaptive) mean |{name}| : {res.sizes.mean():.2f}")
        greedy = heuristic_batch(args.problem, test).sum(-1)
        print(f"greedy mean |{name}|        : {greedy.mean():.2f}")
    if args.problem == "mvc":
        twoapp = matching_2approx_batch(test).sum(-1)
        print(f"2-approx mean |MVC|      : {twoapp.mean():.2f}")
        print(f"reference mean           : {refs.mean():.2f}")


if __name__ == "__main__":
    main()
