"""Traffic driver ``solve``: back-to-back solves of one graph.

Every call goes through the program's ``repro.core.inference.solve`` on
the device engine: one fused ``while_loop`` of policy evaluations, top-d
commits and done checks, ended by one host fetch of the answer.  Each call
starts from a fresh state, so every answer in the window is the same solve
of the same graph from the same weights.

Traffic keys (``workloads/<cell>.json``, ``traffic``):

- ``graph``: ``"er_dense"`` (``n``, ``rho``; dense (1, N, N) adjacency made
  on the device) or ``"ba_csr"`` (``n``, ``d``; CSR arrays made on the
  host);
- ``problem``: the environment (``"mvc"``);
- ``max_d``: the adaptive schedule's commit cap; ``max_evals``: the cap on
  evaluations per solve, or null for full solves.

``infer_step_ms`` is the window's elapsed time over every policy
evaluation completed in it.  The check drives the same compiled solve
program one evaluation per call to get the covers along the way, requires
the last to be the window's answer, and reads the trajectory against the
reference (``reference.check``).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import graphs, reference
from chipbench.harness import Checks, Window, import_program


class Graph:
    """A solve cell's input: what the program gets, and the same graph for
    the reference, built only after the window so it adds nothing to the
    program's memory peak."""

    def __init__(self, traffic: dict, seed: int):
        kind = traffic["graph"]
        self.kind = kind
        if kind == "er_dense":
            self.program_input = graphs.dense_er(traffic["n"],
                                                 traffic["rho"], seed)
            self.n = traffic["n"]
            self.edges = int(jnp.sum(self.program_input, dtype=jnp.int32))
        elif kind == "ba_csr":
            self.arrays = graphs.ba_csr(traffic["n"], traffic["d"], seed)
            indptr, indices, mask = self.arrays
            g = import_program("repro.core.graphs")
            self.program_input = g.CsrGraphBatch(
                indptr=jax.device_put(indptr[None]),
                indices=jax.device_put(indices[None]),
                edge_mask=jax.device_put(mask[None]))
            self.n = traffic["n"]
            self.edges = int(mask.sum())
        else:
            raise ValueError(f"unknown graph kind {kind!r}")

    def for_reference(self):
        if self.kind == "er_dense":
            return reference.Dense(self.program_input[0])
        return reference.csr_from_arrays(*self.arrays)


def program_params(weights: dict):
    """The benchmark's weights as the program's ``PolicyParams``."""
    policy = import_program("repro.core.policy")
    s2v = import_program("repro.core.s2v")
    qmodel = import_program("repro.core.qmodel")
    return policy.PolicyParams(
        em=s2v.S2VParams(*(weights[f"theta{i}"] for i in (1, 2, 3, 4))),
        q=qmodel.QParams(*(weights[f"theta{i}"] for i in (5, 6, 7))))


class Session:
    def __init__(self, cell: dict, config: dict, seed: int):
        self.cell, self.config = cell, config
        traffic = cell["traffic"]
        self.max_evals = traffic.get("max_evals")
        self.weights = graphs.policy_weights(seed, config["embed_dim"],
                                             traffic["n"])
        self.params = program_params(self.weights)
        self.graph = Graph(traffic, seed)
        self.solve = import_program("repro.core.inference").solve
        self.kwargs = dict(num_layers=config["num_layers"], multi_node=True,
                           rep=config["graph_rep"], engine="device",
                           problem=traffic["problem"], max_d=traffic["max_d"],
                           kernel=config["kernel"], compute=config["compute"])
        self.answers: list = []
        self.first = self.one()       # compiles, or loads from the cache

    def one(self):
        res = self.solve(self.params, self.graph.program_input,
                         max_evals=self.max_evals, **self.kwargs)
        return res.solution[0], int(res.policy_evals)

    def window(self, seconds: float) -> Window:
        answers, evals = [], 0
        t0 = now = time.perf_counter()
        while now - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.solve"):
                sol, n_evals = self.one()
            answers.append((sol, n_evals))
            evals += n_evals
            now = time.perf_counter()
        self.answers = answers
        elapsed = now - t0
        return Window(metrics={"infer_step_ms": elapsed * 1e3 / evals},
                      attempted=len(answers), seconds=elapsed,
                      counts={"evals": evals, "nodes": self.graph.n,
                              "edges": self.graph.edges})

    def trajectory(self, evals: int, compute: str | None = None) -> list:
        """The covers after each of ``evals`` policy evaluations, from the
        compiled solve program that ``solve`` runs in the window, driven one
        evaluation per call from its own returned state."""
        engine = import_program("repro.core.engine")
        inference = import_program("repro.core.inference")
        graphrep = import_program("repro.core.graphrep")
        kw = self.kwargs
        rep = graphrep.get_rep(kw["rep"])
        fused = engine.get_solve_step(
            rep=rep, problem=kw["problem"], num_layers=kw["num_layers"],
            use_adaptive=kw["multi_node"], kernel=kw["kernel"],
            compute=compute or kw["compute"], max_d=kw["max_d"],
            donate=False)
        state = inference.init_solve_state(rep, self.graph.program_input,
                                           kw["problem"])
        covers = [np.zeros(self.graph.n, np.float32)]
        one = jnp.asarray(1, jnp.int32)
        for _ in range(evals):
            state, _, _ = fused(self.params, state, one)
            covers.append(np.asarray(state.solution[0]))
        return covers

    def read(self, covers: list, control: str | None = None):
        traffic = self.cell["traffic"]
        cap = self.max_evals or (self.graph.n + traffic["max_d"])
        return reference.check(
            self.weights, self.graph.for_reference(), covers,
            num_layers=self.config["num_layers"], max_d=traffic["max_d"],
            adaptive=True, finished=len(covers) - 1 < cap, control=control)

    def check(self) -> Checks:
        """Every answer of the window must be the cover that the solve
        program reaches one evaluation at a time, and that trajectory must
        follow the reference at every step."""
        limits = self.cell["limits"]
        distinct = {(sol.tobytes(), n): (sol, n) for sol, n in self.answers}
        gap, mismatch = 0.0, 0
        bad = set()
        for key, (sol, n_evals) in distinct.items():
            covers = self.trajectory(n_evals)
            r = self.read(covers)
            m = r.mismatch + int((covers[-1] != sol).sum())
            gap, mismatch = max(gap, r.pick_gap), max(mismatch, m)
            if r.pick_gap > limits["pick_gap"] or m > limits["mismatch"]:
                bad.add(key)
        failed = sum((sol.tobytes(), n) in bad for sol, n in self.answers)
        return Checks(values={"pick_gap": (gap, limits["pick_gap"]),
                              "mismatch": (mismatch, limits["mismatch"])},
                      failed=failed)


def setup(cell: dict, config: dict, seed: int) -> Session:
    return Session(cell, config, seed)
