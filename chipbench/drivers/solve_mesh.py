"""Traffic driver ``solve_mesh``: back-to-back solves of one dense graph
whose adjacency is split by rows over the cell's chips (paper §5.1 spatial
parallelism on §5.3 distributed graph storage).

As the ``solve`` driver does, every call goes through the program's
``repro.core.inference.solve`` on the device engine, here with
``spatial`` set to the configuration's ``mesh`` ([1, P]: one graph, its N
rows split P ways).  The graph is made on the devices by rows and handed
to the program so split: no device ever holds the whole adjacency, and
one that did would not fit it.

Traffic keys (``traffic/<traffic>.json``):

- ``graph``: ``"er_dense_rows"`` (``n``, ``rho``; the ER graph of
  ``graphs.dense_er``, made by rows on the devices, ``graphs_rows.py``);
- ``problem``, ``max_d``, ``max_evals`` as for ``solve``.

``infer_step_ms`` and the check are the ``solve`` driver's, with the
reference of ``reference_rows.py`` reading the same split graph.  The
check drives the compiled solve one evaluation per call, donating each
state it made so that only the graph and one state are alive at a time.
The window's counts add ``chips`` and ``rows_per_chip``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import graphs, graphs_rows, reference_rows
from chipbench.drivers import solve
from chipbench.harness import Window, import_program


class Graph:
    """A row-split dense graph: the program's input, and the same array
    for the reference."""

    def __init__(self, traffic: dict, seed: int, mesh, axis: str):
        if traffic["graph"] != "er_dense_rows":
            raise ValueError(f"unknown graph kind {traffic['graph']!r}")
        self.n = traffic["n"]
        self.rows_per_chip = self.n // mesh.shape[axis]
        self.program_input = graphs_rows.dense_er_rows(
            self.n, traffic["rho"], seed, mesh, axis)
        self.edges = int(jnp.sum(self.program_input, dtype=jnp.int32))

    def for_reference(self):
        return reference_rows.DenseRows(self.program_input)


class Session(solve.Session):
    def __init__(self, cell: dict, config: dict, seed: int):
        self.cell, self.config = cell, config
        traffic = cell["traffic"]
        dp, sp = config["mesh"]
        if dp != 1:
            raise ValueError(f"solve_mesh solves one graph; mesh {dp, sp}")
        self.chips = cell["chips"]
        self.spatial = (dp, sp)
        # the graph is made on the program's own (data, graph) mesh, so
        # the program finds it laid out as its solve holds it
        mesh = import_program("repro.core.mesh").make_mesh(dp, sp)
        self.max_evals = traffic.get("max_evals")
        self.weights = graphs.policy_weights(seed, config["embed_dim"],
                                             traffic["n"])
        self.params = solve.program_params(self.weights)
        self.graph = Graph(traffic, seed, mesh, "graph")
        self.solve = import_program("repro.core.inference").solve
        self.kwargs = dict(num_layers=config["num_layers"], multi_node=True,
                           rep=config["graph_rep"], engine="device",
                           problem=traffic["problem"], max_d=traffic["max_d"],
                           kernel=config["kernel"], compute=config["compute"],
                           spatial=self.spatial)
        self.answers: list = []
        self.first = self.one()       # compiles, or loads from the cache

    def window(self, seconds: float) -> Window:
        w = super().window(seconds)
        w.counts.update(chips=self.chips,
                        rows_per_chip=self.graph.rows_per_chip)
        return w

    def trajectory(self, evals: int, compute: str | None = None) -> list:
        """The covers after each of ``evals`` policy evaluations, from the
        compiled solve program the window runs, one evaluation per call.
        The first call reads the benchmark's graph and keeps it; every
        later call donates the state the previous one made."""
        engine = import_program("repro.core.engine")
        inference = import_program("repro.core.inference")
        graphrep = import_program("repro.core.graphrep")
        mesh = import_program("repro.core.mesh")
        kw = self.kwargs
        rep = graphrep.get_rep(kw["rep"])
        steps = [engine.get_solve_step(
            rep=rep, problem=kw["problem"], num_layers=kw["num_layers"],
            use_adaptive=kw["multi_node"], spatial=self.spatial,
            kernel=kw["kernel"], compute=compute or kw["compute"],
            max_d=kw["max_d"], donate=donate) for donate in (False, True)]
        state = inference.init_solve_state(rep, self.graph.program_input,
                                           kw["problem"])
        state = mesh.shard_solve_state(mesh.make_mesh(*self.spatial), state)
        covers = [np.zeros(self.graph.n, np.float32)]
        one = jnp.asarray(1, jnp.int32)
        for t in range(evals):
            state, _, _ = steps[t > 0](self.params, state, one)
            covers.append(np.asarray(state.solution[0]))
        return covers


def setup(cell: dict, config: dict, seed: int) -> Session:
    return Session(cell, config, seed)
