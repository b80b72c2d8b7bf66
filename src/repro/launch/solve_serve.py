"""Graph-solver service launcher: drive a heterogeneous-size request
stream through the serving layer + fused inference engine (DESIGN.md
§9/§14), in either the sync drain path or the async SLO-aware path.

    # one-shot stream, sync drain (back-compat default)
    PYTHONPATH=src python -m repro.launch.solve_serve \
        --requests 12 --sizes 12,20,28 --rep sparse

    # async continuous batching with AOT warmup and per-request latency
    PYTHONPATH=src python -m repro.launch.solve_serve \
        --mode async --warmup --deadline-ms 200

    # open-loop Poisson load test at a fixed offered rate (rps)
    PYTHONPATH=src python -m repro.launch.solve_serve \
        --mode async --rate 50 --requests 200 --warmup
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default=None,
                    help="load policy params from a repro.checkpoint "
                         "snapshot (default: fresh random policy)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--sizes", default="12,20,28",
                    help="comma-separated node counts the stream mixes")
    ap.add_argument("--kind", choices=["er", "ba", "social"], default="er")
    ap.add_argument("--problem", default="mvc",
                    choices=["mvc", "maxcut", "mis", "mds"],
                    help="registered environment to solve: mvc (min vertex "
                         "cover), maxcut (max cut), mis (max independent "
                         "set), mds (min dominating set); all four serve "
                         "through the same padded buckets — the registry's "
                         "padding-safety contract guarantees isolated "
                         "padding nodes never score or commit")
    ap.add_argument("--rep", choices=["dense", "sparse", "csr"], default="dense")
    ap.add_argument("--spatial", default="0",
                    help="2-D (data, graph) mesh spec: 'dp,sp' shards each "
                         "bucket dispatch dp ways over the batch (data "
                         "axis; --max-batch becomes per-device) and every "
                         "policy eval sp ways over node rows; a bare int P "
                         "means the legacy node sharding (1, P); 0 → "
                         "single device")
    ap.add_argument("--collectives", default="auto",
                    choices=["auto", "manual", "gspmd"],
                    help="cross-shard strategy carried on the PolicyConfig "
                         "(DESIGN.md §10); inference is unaffected but the "
                         "choice rides along for any co-located training")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--embed-dim", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    # -- async / SLO knobs (DESIGN.md §14) ----------------------------------
    ap.add_argument("--mode", choices=["sync", "async"], default="sync",
                    help="sync: queue everything and drain() once; async: "
                         "submit futures against the background scheduler "
                         "thread (continuous batching)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s; > 0 switches to an "
                         "open-loop Poisson arrival process (the latency-"
                         "measurement harness, serving/loadgen.py) instead "
                         "of a burst")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency SLO; drives EDF scheduling "
                         "and the goodput (on-time completions) accounting")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="max head-of-queue wait before an underfilled "
                         "bucket dispatches partial")
    ap.add_argument("--queue-depth", type=int, default=512,
                    help="admission bound: submissions beyond this depth "
                         "are fast-rejected (ServiceOverloaded)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every (bucket, problem) executable "
                         "before the first request (zero cold compiles on "
                         "the request path)")
    args = ap.parse_args()

    import jax
    from ..core import PolicyConfig, init_policy, parse_spatial
    from ..core.graphs import erdos_renyi, barabasi_albert, social_like
    from ..compile_cache import setup_compile_cache
    from ..serving import GraphSolverService, make_workload, run_open_loop

    setup_compile_cache()

    cfg = PolicyConfig(embed_dim=args.embed_dim, num_layers=2,
                       graph_rep=args.rep,
                       spatial=parse_spatial(args.spatial),
                       collectives=args.collectives)
    svc_kw = dict(max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
                  max_queue_depth=args.queue_depth,
                  default_deadline_ms=args.deadline_ms)
    if args.ckpt_dir:
        svc = GraphSolverService.from_checkpoint(args.ckpt_dir, cfg, **svc_kw)
        print(f"policy loaded from {args.ckpt_dir}")
    else:
        params = init_policy(jax.random.key(args.seed), cfg)
        svc = GraphSolverService(params, cfg, **svc_kw)
        print("fresh random policy (pass --ckpt-dir for a trained one)")

    sizes = [int(s) for s in args.sizes.split(",")]
    if args.warmup:
        info = svc.warmup(sizes, problems=[args.problem])
        print(f"warmup: {len(info['compiled'])} executables in "
              f"{info['seconds']:.2f}s -> request path compiles == 0")

    if args.rate > 0:
        wl = make_workload(args.rate, args.requests, sizes,
                           problem=args.problem, kind=args.kind,
                           deadline_ms=args.deadline_ms, seed=args.seed)
        rep = run_open_loop(svc, wl, mode=args.mode)
        svc.close()
        print(f"{rep.mode} @ {args.rate:.1f} rps offered: "
              f"p50 {rep.p50_ms:.1f}ms p99 {rep.p99_ms:.1f}ms, "
              f"goodput {rep.goodput_rps:.1f} rps "
              f"({rep.on_time}/{rep.submitted} on time, "
              f"{rep.rejected} shed)")
    else:
        gen = {"er": lambda n, s: erdos_renyi(n, 0.2, seed=s),
               "ba": lambda n, s: barabasi_albert(n, 4, seed=s),
               "social": lambda n, s: social_like(n, seed=s)}[args.kind]
        rng = np.random.default_rng(args.seed)
        adjs = [gen(int(rng.choice(sizes)), args.seed + i)
                for i in range(args.requests)]
        t0 = time.time()
        if args.mode == "async":
            futures = [svc.submit_async(a, problem=args.problem)
                       for a in adjs]
            responses = [f.result() for f in futures]
            svc.close()
        else:
            responses = svc.serve(adjs, problem=args.problem)
        dt = time.time() - t0
        for r in responses:
            n = len(r.solution)
            lat = (f"  lat={r.latency_s * 1e3:6.1f}ms"
                   if r.complete_t else "")
            print(f"  req{r.id:3d}  n={n:4d} -> bucket {r.bucket:4d}  "
                  f"|S|={r.size:4d}  evals={r.policy_evals}{lat}")
        s = svc.stats
        print(f"served {s.requests} requests in {dt:.2f}s: "
              f"{s.batches} batches ({s.partial_batches} partial), "
              f"{s.compiles} request-path compiles "
              f"(+{s.warmup_compiles} warmup, {s.compile_seconds:.2f}s), "
              f"{s.cache_hits} cache hits, {s.padded_rows} padded rows, "
              f"{s.solve_seconds:.2f}s on-device solve")


if __name__ == "__main__":
    main()
