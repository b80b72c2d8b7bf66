#!/usr/bin/env python3
"""Bring-up run of the structure2vec graph-RL main path on a TPU.

    python3 chip_smoke.py              # one chip: kernels, train, solve, serve
    python3 chip_smoke.py --chips 4    # four chips: the spatial mesh path
                                       # and its one-device reference only

The policy is the paper's: structure2vec with K=32, L=2, minibatch 64 and
a 50k-tuple replay (``configs/papergraph.py``), with random weights made
from ``--seed``; every graph is generated from ``--seed`` as well.  The
one-chip phases, each a function that takes its sizes as arguments:

- kernels: each main-path Pallas kernel (dense fused, ``mp_aggregate``,
  padded-sparse fused, gather, CSR) compiled (``interpret=False``) at the
  train shapes and compared with its ``kernels/ref.py`` oracle, run on the
  host CPU, at the tolerances of the CPU parity tests;
- train: ``train_agent`` on the device engine for MVC on each rep;
- solve: ``inference.solve`` on the paper's largest graph (ER N=21,000,
  rho=0.15, dense, capped at 64 evaluations) and full solves of a BA
  N=16,384 d=10 graph on the sparse and CSR reps, each answer checked by
  the env checker and set against ``solvers.heuristic_batch``;
- serve: ``GraphSolverService`` in async mode after ``warmup()``.

Each phase prints its wall time and the device's peak bytes; each rep
prints which S2V layer implementation the size rule chose.  Any failure
exits non-zero.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script refuses to run when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.compile_cache import setup_compile_cache        # noqa: E402
from repro.configs.papergraph import CONFIG as PAPER       # noqa: E402
from repro.core import (Agent, env, get_rep, init_policy,  # noqa: E402
                        solve, train_agent)
from repro.core.graphs import (cached_ba_csr,               # noqa: E402
                               csr_batch_from_arrays, csr_batch_from_dense,
                               csr_row_ids, erdos_renyi, random_graph_batch,
                               sparse_batch_from_dense)
from repro.core.s2v import s2v_layer_impl                   # noqa: E402
from repro.core.solvers import heuristic_batch              # noqa: E402
from repro.kernels import ref                               # noqa: E402
from repro.kernels.s2v_csr import fused_s2v_layer_csr       # noqa: E402
from repro.kernels.s2v_fused import (fused_s2v_layer,       # noqa: E402
                                     fused_s2v_layer_sparse, mp_aggregate)
from repro.kernels.s2v_gather import sparse_mp_aggregate    # noqa: E402

K = PAPER.embed_dim
# f32 tolerances of the CPU parity tests (tests/test_kernels.py,
# tests/test_fused_kernel.py, tests/test_csr.py); bf16 runs compare with the
# oracle on bf16-rounded inputs at the tests' BF16_TOL.
F32_TOL = {"dense_fused": 1e-4, "mp_aggregate": 1e-5, "sparse_fused": 1e-6,
           "gather": 1e-5, "csr_fused": 1e-6}
BF16_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or unusable result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(*parts) -> None:
    print(*parts, flush=True)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_phase(name: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    log(f"PHASE {name}: wall {time.perf_counter() - t0:.3f} s, "
        f"device peak_bytes_in_use {peak_bytes()}")
    return out


def report_impl(phase: str, rep: str, **shapes) -> str:
    impl = s2v_layer_impl(rep, k=K, **shapes)
    log(f"IMPL {phase} rep={rep} {shapes} -> {impl}")
    return impl


def _on_host(fn, *args):
    """Run an oracle on the host CPU in plain f32, as the parity tests do."""
    cpu = jax.devices("cpu")[0]
    args = jax.device_put([np.asarray(a, np.float32) for a in args], cpu)
    with jax.default_device(cpu):
        return np.asarray(fn(*args))


def _compare(name: str, got, want, tol: float) -> float:
    got = np.asarray(got, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    bound = float((tol + tol * np.abs(want)).max())
    ok = bool(np.all(np.abs(got - want) <= tol + tol * np.abs(want)))
    log(f"  {name}: max|d| {err:.3e} (rtol=atol={tol:g}) "
        f"{'ok' if ok else 'FAIL'}")
    check(ok, f"{name}: max|d| {err:.3e} beyond rtol=atol={tol:g} "
              f"(largest allowed {bound:.3e})")
    return err


def phase_kernels(*, batch: int, n: int, rho: float, ba_degree: int,
                  seed: int, interpret: bool = False) -> dict:
    """Every main-path S2V kernel at K on the train shapes against its
    ``kernels/ref.py`` oracle: f32, and bf16 where the kernel takes a
    compute dtype."""
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return (rng.random(shape, np.float32) - 0.5).astype(np.float32)

    er = random_graph_batch("er", n, batch, seed=seed, rho=rho)
    ba = random_graph_batch("ba", n, batch, seed=seed, d=ba_degree)
    t4, embed, base = rand(K, K) * 0.2, rand(batch, K, n), rand(batch, K, n)
    g = sparse_batch_from_dense(ba)
    nbr = np.asarray(g.neighbors)
    edge = np.asarray(g.valid, np.float32) * rng.random(
        nbr.shape).astype(np.float32)
    xs = np.concatenate([embed, np.zeros((batch, K, 1), np.float32)], -1)
    c = csr_batch_from_dense(ba)
    rid = np.asarray(csr_row_ids(c.indptr, c.indices.shape[1]))
    idx = np.asarray(c.indices)
    ew = np.asarray(c.edge_mask, np.float32) * rng.random(
        idx.shape).astype(np.float32)
    report_impl("kernels", "dense", n=n)
    report_impl("kernels", "sparse", max_degree=nbr.shape[2])
    report_impl("kernels", "csr", n=n)

    cases = {
        "dense_fused": (lambda cd, t, e, a, b: fused_s2v_layer(
            t, e, a, b, compute_dtype=cd, interpret=interpret),
            ref.s2v_layer, (t4, embed, er, base)),
        "mp_aggregate": (lambda cd, e, a: mp_aggregate(
            e, a, compute_dtype=cd, interpret=interpret),
            ref.mp_aggregate, (embed, er)),
        "sparse_fused": (lambda cd, t, x, b: fused_s2v_layer_sparse(
            t, x, nbr, edge, b, compute_dtype=cd, interpret=interpret),
            lambda t, x, b: ref.s2v_layer_sparse(t, x, nbr, edge, b),
            (t4, embed, base)),
        "gather": (lambda cd, x: sparse_mp_aggregate(
            x, nbr, edge, interpret=interpret),
            lambda x: ref.sparse_mp_aggregate(x, nbr, edge), (xs,)),
        "csr_fused": (lambda cd, t, x, b: fused_s2v_layer_csr(
            t, x, idx, rid, ew, b, compute_dtype=cd, interpret=interpret),
            lambda t, x, b: ref.s2v_layer_csr(t, x, idx, rid, ew, b),
            (t4, embed, base)),
    }
    errors = {}
    for name, (kernel, oracle, args) in cases.items():
        want = _on_host(oracle, *args)
        errors[name] = _compare(f"{name} f32",
                                kernel(jnp.float32, *args), want,
                                F32_TOL[name])
        if name == "gather":                # f32-only kernel
            continue
        rounded = [np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
                   for a in args]
        want16 = _on_host(oracle, *rounded)
        errors[name + "_bf16"] = _compare(f"{name} bf16",
                                          kernel(jnp.bfloat16, *args),
                                          want16, BF16_TOL)
    return errors


def _params_moved(before, after) -> float:
    return max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(before),
                               jax.tree.leaves(after)))


def phase_train(rep: str, *, graphs: int, n: int, steps: int, tau: int,
                seed: int, cfg=PAPER, **graph_kw) -> dict:
    """``train_agent`` on the device engine, MVC, ``graphs`` env graphs
    stepped together for ``steps`` env steps with ``tau`` GD iterations."""
    kind = "er" if rep == "dense" else "ba"
    adj = random_graph_batch(kind, n, graphs, seed=seed, **graph_kw)
    cfg = dataclasses.replace(cfg, graph_rep=rep, engine="device")
    shapes = {"dense": {"n": n}, "csr": {"n": n},
              "sparse": {"max_degree": int((adj > 0).sum(-1).max())}}[rep]
    impl = report_impl("train", rep, **shapes)
    agent = Agent(cfg, num_nodes=n,
                  params=init_policy(jax.random.key(seed), cfg))
    before = jax.tree.map(np.asarray, agent.params)
    log_ = train_agent(agent, adj, problem="mvc", episodes=steps, tau=tau,
                       batch_graphs=graphs, max_steps=steps, seed=seed,
                       engine="device")
    moved = _params_moved(before, agent.params)
    loss = log_.losses[-1]
    log(f"  train {rep}: {len(log_.losses)} env steps, tau={tau}, "
        f"final loss {loss:.6g}, max|param delta| {moved:.3e}, "
        f"wall {log_.wall_time:.3f} s")
    check(len(log_.losses) == steps, f"train {rep}: ran "
                                     f"{len(log_.losses)} of {steps} steps")
    check(bool(np.isfinite(loss)), f"train {rep}: final loss {loss}")
    check(moved > 0.0, f"train {rep}: params did not move")
    return {"loss": loss, "param_delta": moved, "impl": impl}


def _check_answer(name: str, problem: str, adj: np.ndarray,
                  solution: np.ndarray) -> float:
    feasible = np.asarray(env.checker(problem)(jnp.asarray(adj),
                                               jnp.asarray(solution)))
    check(bool(feasible.all()), f"{name}: infeasible {problem} answer")
    greedy = heuristic_batch(problem, adj).sum(-1)
    ratio = float(np.mean(solution.sum(-1) / np.maximum(greedy, 1)))
    log(f"  {name}: feasible, |S| {solution.sum(-1).tolist()}, "
        f"greedy {greedy.tolist()}, ratio to greedy {ratio:.4f}")
    return ratio


def phase_solve_dense(*, n: int, rho: float, max_evals: int,
                      seed: int, cfg=PAPER) -> dict:
    """``inference.solve`` on one dense ER graph, capped at ``max_evals``
    evaluations: the first call compiles, the second is timed."""
    params = init_policy(jax.random.key(seed), cfg)
    t0 = time.perf_counter()
    host = erdos_renyi(n, rho, seed=seed)[None]
    edges = int(np.count_nonzero(host)) // 2
    adj = jax.block_until_ready(jax.device_put(host))
    del host
    log(f"  dense N={n}: {edges} edges, graph made and placed in "
        f"{time.perf_counter() - t0:.3f} s")
    impl = report_impl("solve", "dense", n=n)
    kw = dict(num_layers=cfg.num_layers, multi_node=True, rep="dense")
    t0 = time.perf_counter()
    first = solve(params, adj, max_evals=1, **kw)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = solve(params, adj, max_evals=max_evals, **kw)
    t_run = time.perf_counter() - t0
    per_eval = t_run / res.policy_evals
    log(f"  dense N={n}: first call (compile + 1 eval) {t_first:.3f} s, "
        f"compile ~{t_first - per_eval:.3f} s; {res.policy_evals} evals "
        f"in {t_run:.3f} s = {per_eval * 1e3:.3f} ms/eval; "
        f"{int(res.sizes[0])} nodes committed")
    check(first.policy_evals == 1, "dense solve: first call ran "
                                   f"{first.policy_evals} evals")
    check(res.policy_evals == max_evals,
          f"dense solve: {res.policy_evals} evals, expected the cap "
          f"{max_evals}")
    committed = int(res.nodes_committed.sum())
    check(committed == int(res.sizes.sum()) and committed > 0,
          f"dense solve: {committed} commits vs |S| {res.sizes}")
    return {"compile_s": t_first - per_eval, "ms_per_eval": per_eval * 1e3,
            "impl": impl}


def ba_graph(n: int, d: int, seed: int):
    """BA(n, d) from the streaming generator: its CSR arrays and the dense
    (1, N, N) adjacency the checkers and the greedy baseline read."""
    indptr, indices = cached_ba_csr(n, d, seed=seed)
    dense = np.zeros((1, n, n), np.float32)
    dense[0, np.repeat(np.arange(n), np.diff(indptr)), indices] = 1.0
    return indptr, indices, dense


def phase_solve_ba(rep: str, *, n: int, d: int, max_d: int, seed: int,
                   cfg=PAPER) -> dict:
    """One full adaptive MVC solve of a BA graph on the sparse or CSR rep."""
    params = init_policy(jax.random.key(seed), cfg)
    indptr, indices, dense = ba_graph(n, d, seed)
    if rep == "csr":
        graph = csr_batch_from_arrays(indptr, indices)
        impl = report_impl("solve", rep, n=n)
    else:
        graph = sparse_batch_from_dense(dense)
        impl = report_impl("solve", rep,
                           max_degree=int(graph.neighbors.shape[2]))
    kw = dict(num_layers=cfg.num_layers, multi_node=True, rep=rep,
              max_d=max_d)
    t0 = time.perf_counter()
    solve(params, graph, max_evals=1, **kw)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = solve(params, graph, **kw)
    wall = time.perf_counter() - t0
    per_eval = wall / res.policy_evals
    log(f"  {rep} BA N={n} d={d}: {len(indices)} directed edges; first "
        f"call (compile + 1 eval) {t_first:.3f} s; full solve "
        f"{res.policy_evals} evals (max_d={max_d}) in {wall:.3f} s = "
        f"{per_eval * 1e3:.3f} ms/eval")
    ratio = _check_answer(f"{rep} solve", "mvc", dense, res.solution)
    return {"evals": res.policy_evals, "ms_per_eval": per_eval * 1e3,
            "ratio": ratio, "impl": impl}


def phase_serve(*, requests: int, min_n: int, max_n: int, seed: int,
                cfg=PAPER, max_batch: int = 4) -> dict:
    """``GraphSolverService`` in async mode after ``warmup()``: ``requests``
    graphs of ``min_n``..``max_n`` nodes mixed over the four problems."""
    from repro.serving import GraphSolverService
    rng = np.random.default_rng(seed)
    problems = ("mvc", "maxcut", "mis", "mds")
    sizes = rng.integers(min_n, max_n + 1, size=requests)
    sizes[:2] = (min_n, max_n)
    probs = [problems[i % len(problems)] for i in range(requests)]
    adjs = [erdos_renyi(int(s), 0.15, seed=seed + i)
            for i, s in enumerate(sizes)]
    params = init_policy(jax.random.key(seed), cfg)
    svc = GraphSolverService(params, cfg, max_batch=max_batch)
    try:
        t0 = time.perf_counter()
        for p in problems:
            svc.warmup([int(s) for s, q in zip(sizes, probs) if q == p],
                       problems=[p])
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        futures = [svc.submit_async(a, problem=p) for a, p in zip(adjs, probs)]
        responses = [f.result(timeout=600) for f in futures]
        wall = time.perf_counter() - t0
    finally:
        svc.close()
    for a, p, r in zip(adjs, probs, responses):
        ok = bool(np.asarray(env.checker(p)(
            jnp.asarray(a)[None], jnp.asarray(r.solution, jnp.float32)[None]
        ))[0])
        check(ok, f"serve: request {r.id} ({p}, n={len(a)}) infeasible")
    lat = np.array([r.latency_s for r in responses]) * 1e3
    s = svc.stats
    log(f"  serve: {requests} requests ({sorted(set(probs))}, n "
        f"{int(sizes.min())}..{int(sizes.max())}) all feasible; warmup "
        f"{s.warmup_compiles} compiles in {warm:.3f} s; {s.batches} batches "
        f"in {wall:.3f} s; request-path compiles {s.compiles}; latency p50 "
        f"{np.percentile(lat, 50):.3f} ms p99 {np.percentile(lat, 99):.3f} ms")
    check(s.compiles == 0, f"serve: {s.compiles} compiles after warmup")
    return {"wall_s": wall, "p50_ms": float(np.percentile(lat, 50))}


def _shard_shapes(name: str, x) -> list:
    shapes = sorted((str(s.device), tuple(s.data.shape))
                    for s in x.addressable_shards)
    log(f"  shards {name}: " + ", ".join(f"{d} {sh}" for d, sh in shapes))
    return shapes


def phase_mesh_solve(*, n: int, rho: float, spatial: tuple, max_evals: int,
                     seed: int, cfg=PAPER, tol: float = 1e-5) -> dict:
    """Dense policy scores at ``spatial`` against the one-device reference,
    relative to the scores' magnitude (they grow as N³ with random
    weights: about 1e5 at N=21,000, where one f32 ulp is 8e-3), then a
    capped solve on the mesh from an adjacency split by rows, each device
    holding only its (1, N/sp, N) block.  Near-tied scores make the top-d
    picks of the two solves differ by rounding, so the solve reports, and
    does not gate on, the nodes where they differ."""
    from jax.sharding import NamedSharding
    from repro.core import make_mesh, policy_scores, spatial_scores_fn
    from repro.core.graphs import init_state
    from repro.core.mesh import DENSE_STATE_SPECS, shard_rows
    params = init_policy(jax.random.key(seed), cfg)
    adj = erdos_renyi(n, rho, seed=seed)[None]
    st = init_state(jax.device_put(adj, jax.devices()[0]))
    want = np.asarray(jax.jit(lambda p, s: policy_scores(
        p, s.adj, s.solution, s.candidate, num_layers=cfg.num_layers))(
            params, st))
    mesh = make_mesh(*spatial)
    # node rows over `graph`: the layout each policy evaluation runs on
    tiled = [jax.device_put(x, NamedSharding(mesh, spec)) for x, spec in
             zip((st.adj, st.solution, st.candidate), DENSE_STATE_SPECS)]
    _shard_shapes(f"dense adj at {spatial}", tiled[0])
    scorer = jax.jit(spatial_scores_fn(mesh, cfg.num_layers))
    got = np.asarray(scorer(params, *tiled))
    del st, tiled
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / scale
    log(f"  scores at {spatial} vs one device: max|d|/max|score| "
        f"{err:.3e} (max|score| {scale:.6e})")
    check(err <= tol, f"mesh scores: relative max|d| {err:.3e} > {tol:g}")
    kw = dict(num_layers=cfg.num_layers, multi_node=True, rep="dense",
              max_evals=max_evals)
    ref_res = solve(params, adj, **kw)
    # the solve's input split by rows as its carry holds it: (B/dp, N/sp,
    # N) on each device, never the whole graph
    rows = shard_rows(mesh, adj)
    shapes = _shard_shapes(f"dense solve input at {spatial}", rows)
    want = (1, n // spatial[1], n)
    check(all(sh == want for _, sh in shapes),
          f"mesh solve input shards {shapes}, want {want} on each device")
    t0 = time.perf_counter()
    res = solve(params, rows, spatial=spatial, **kw)
    wall = time.perf_counter() - t0
    differ = int((res.solution != ref_res.solution).sum())
    log(f"  capped solve at {spatial}: {res.policy_evals} evals in "
        f"{wall:.3f} s incl. compile, |S| {int(res.sizes[0])} vs one "
        f"device {int(ref_res.sizes[0])}, {differ} nodes differ")
    check(res.policy_evals == ref_res.policy_evals == max_evals,
          f"mesh solve ran {res.policy_evals} evals, one device "
          f"{ref_res.policy_evals}, cap {max_evals}")
    return {"score_rel_err": err, "nodes_differ": differ}


def phase_mesh_train(*, spatial: tuple, collectives: str, graphs: int,
                     n: int, steps: int, tau: int, seed: int, cfg=PAPER,
                     tol: float = 1e-5) -> dict:
    """The fused train step on the ``spatial`` mesh against one device:
    ``steps`` greedy (eps=0) steps from the same seed; params compared
    absolutely, TD losses relative to their magnitude."""
    from repro.core import engine_init, get_train_step, mesh_from_spec
    from repro.core.mesh import shard_batch
    from repro.optim import adam_init
    adj = random_graph_batch("er", n, graphs, seed=seed, rho=0.15)
    base = dataclasses.replace(cfg, eps_start=0.0, eps_end=0.0,
                               learning_rate=1e-3, replay_capacity=512)

    def run(spec):
        c = dataclasses.replace(base, spatial=spec, collectives=collectives)
        rep = get_rep("dense")
        params = init_policy(jax.random.key(seed), c)
        fused = get_train_step(c, rep=rep, tau=tau)
        mesh = mesh_from_spec(spec)
        es = engine_init(c, params, adam_init(params), n, seed=seed,
                         mesh=mesh)
        source = rep.prepare_dataset(adj)
        gi = np.arange(graphs, dtype=np.int32)
        state = rep.state_from_tuples(source, gi,
                                      np.zeros((graphs, n), np.float32))
        if mesh is not None:
            state = shard_batch(mesh, state)
        losses = []
        for _ in range(steps):
            es, state, _a, _r, _d, loss = fused(es, state, source,
                                                jnp.asarray(gi))
            losses.append(float(loss))
        return es, np.asarray(losses)

    ref_es, ref_losses = run(0)
    es, losses = run(spatial)
    _shard_shapes(f"replay solution at {spatial}", es.replay.solution)
    err = _params_moved(ref_es.params, es.params)
    warm = np.isfinite(ref_losses)
    lerr = float((np.abs(losses[warm] - ref_losses[warm])
                  / np.maximum(np.abs(ref_losses[warm]), 1.0)).max())
    moved = _params_moved(init_policy(jax.random.key(seed), base),
                          ref_es.params)
    log(f"  train at {spatial} ({collectives}) vs one device: max|param d| "
        f"{err:.3e}, max relative loss d {lerr:.3e} over {int(warm.sum())} "
        f"warm steps; params moved {moved:.3e} from init")
    check(warm.any() and moved > 0, "mesh train: reference did not train")
    check(err <= tol and lerr <= tol,
          f"mesh train at {spatial}: param {err:.3e} / loss {lerr:.3e} "
          f"> {tol:g}")
    return {"param_err": err, "loss_err": lerr}


def require_tpu(chips: int) -> dict:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{platform!r}); refusing to run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips requested, JAX sees "
                         f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the spatial mesh path and its "
                         "one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    log(f"device: {device}; compile cache {setup_compile_cache()}")
    seed = args.seed
    if args.chips == 4:
        run_phase("mesh_solve", phase_mesh_solve, n=21_000, rho=0.15,
                  spatial=(1, 4), max_evals=8, seed=seed)
        for spec, coll in (((2, 2), "manual"), ((4, 1), "auto")):
            run_phase(f"mesh_train_{spec[0]}x{spec[1]}", phase_mesh_train,
                      spatial=spec, collectives=coll, graphs=16, n=1024,
                      steps=6, tau=4, seed=seed)
    else:
        run_phase("kernels", phase_kernels, batch=8, n=1024, rho=0.15,
                  ba_degree=4, seed=seed)
        run_phase("train_dense", phase_train, "dense", graphs=8, n=1024,
                  steps=20, tau=4, seed=seed, rho=0.15)
        for rep in ("sparse", "csr"):
            run_phase(f"train_{rep}", phase_train, rep, graphs=8, n=1024,
                      steps=20, tau=4, seed=seed, d=4)
        run_phase("solve_dense_w1", phase_solve_dense, n=21_000, rho=0.15,
                  max_evals=64, seed=seed)
        for rep in ("sparse", "csr"):
            run_phase(f"solve_{rep}", phase_solve_ba, rep, n=16_384, d=10,
                      max_d=16_384 // 16, seed=seed)
        run_phase("serve", phase_serve, requests=16, min_n=64, max_n=1024,
                  seed=seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
