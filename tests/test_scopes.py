"""The solve path's trace names: four named scopes on the device program and
three profiler spans on the host.

The scopes live only in the compiled program's metadata (each instruction's
``op_name``), so a device trace can attribute time to ``s2v.embed``,
``q.head``, ``env.select`` and ``env.commit`` by name, whatever numbers XLA
gives its fusions.  The spans ``solve.prepare``, ``solve.dispatch`` and
``solve.fetch`` label what the host does around each fused solve.
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import engine, graphrep, inference
from repro.core.graphs import erdos_renyi
from repro.core.policy import PolicyConfig, init_policy

N = 64
SCOPES = ("s2v.embed", "q.head", "env.select", "env.commit")
SPANS = ("solve.prepare", "solve.dispatch", "solve.fetch")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ")
_ARRAY = re.compile(r"\w+\[([0-9,]*)\]")


def _graph():
    return erdos_renyi(N, 0.15, seed=3)[None]


def _elements(shape: str) -> int:
    """The largest array of an HLO shape (a tuple's largest member)."""
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in _ARRAY.findall(shape)]
    return max(sizes, default=0)


def _while_body(hlo: str) -> list:
    """(name, elements, op_name or None) of each instruction of the solve
    loop's body computation in compiled HLO text."""
    loop = next(line for line in hlo.splitlines()
                if " while(" in line and 'op_name="jit(solve_fn)/while"'
                in line)
    body = re.search(r"body=%([\w.\-]+)", loop).group(1)
    lines = hlo[hlo.index(f"\n%{body} "):].splitlines()[2:]
    out = []
    for line in lines[:lines.index("}")]:
        m = _INSTRUCTION.match(line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(1), _elements(m.group(2)),
                    op_name.group(1) if op_name else None))
    return out


@pytest.mark.parametrize("rep", ["dense", "csr"])
def test_solve_loop_body_runs_under_the_four_scopes(rep):
    """Every instruction of the compiled loop body that holds at least N
    values, and that XLA made from the program's own code, carries one of
    the four scopes as a component of its ``op_name``; each scope is
    there.  Instructions XLA adds itself carry no ``op_name`` at all (on
    the CPU: split reductions, broadcasts of constants, a re-laid dot)."""
    r = graphrep.get_rep(rep)
    params = init_policy(jax.random.key(0), PolicyConfig())
    state = inference.init_solve_state(r, _graph(), "mvc")
    fn = engine.get_solve_step(rep=r, problem="mvc", num_layers=2,
                               use_adaptive=True, max_d=8, donate=False)
    hlo = fn.lower(params, state, jnp.int32(N + 8)).compile().as_text()
    body = _while_body(hlo)
    named = [(name, op) for name, size, op in body if op is not None]
    unscoped = [(name, op) for name, size, op in body
                if op is not None and size >= N
                and not set(op.split("/")) & set(SCOPES)]
    assert unscoped == []
    seen = {s for _, op in named for s in SCOPES if s in op.split("/")}
    assert seen == set(SCOPES)


def _host_spans(path) -> list:
    """(name, start_ns, end_ns) of every solve span on the host planes."""
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in SPANS)
    return sorted(spans, key=lambda s: s[1])


@pytest.mark.parametrize("rep", ["dense", "csr"])
def test_solve_emits_the_host_spans(rep, tmp_path):
    """One device-engine solve emits solve.prepare, solve.dispatch and
    solve.fetch once each, in that order and without overlap."""
    params = init_policy(jax.random.key(0), PolicyConfig())
    kw = dict(rep=rep, multi_node=True, engine="device")
    want = inference.solve(params, _graph(), **kw)      # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = inference.solve(params, _graph(), **kw)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(got.solution, want.solution)
    spans = _host_spans(next(tmp_path.rglob("*.xplane.pb")))
    assert [s[0] for s in spans] == list(SPANS)
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
