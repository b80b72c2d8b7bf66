"""The join of a device trace to the program's named scopes and host spans
(``chipbench/scopes.py``), and the readers ``s2v_ms.infer`` and
``commit_ms.infer`` that use it."""
import pathlib
import re
import shutil

import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, trace
from chipbench.harness import Window

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[1]


# -- a synthetic trace with embedded HLO ---------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A serialized message from (field number, int | str | bytes)."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _hlo(module: str, computations: dict) -> bytes:
    """An HloProto: computation id -> {instruction name: op_name, or
    (op_name, called computation ids)}."""
    def instruction(name, spec):
        op, called = spec if isinstance(spec, tuple) else (spec, ())
        packed = b"".join(_varint(c) for c in called)
        return _msg((1, name), (2, "fusion"), (7, _msg((2, op))),
                    *([(38, packed)] if packed else []))
    return _msg((1, _msg((1, module), *[
        (3, _msg((1, f"c{cid}"), *[(2, instruction(n, spec))
                                   for n, spec in rows.items()], (5, cid)))
        for cid, rows in computations.items()])))


def _metadata_plane(modules: dict) -> bytes:
    """A ``/host:metadata`` plane holding each module's HLO as the
    ``Hlo Proto`` stat of an event-metadata entry named after it."""
    entries = [(4, _msg((1, i), (2, _msg(
        (1, i), (2, name), (5, _msg((1, 7), (6, _hlo(name, comps))))))))
        for i, (name, comps) in enumerate(modules.items(), start=1)]
    stat = (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto")))))
    return _msg((1, _msg((1, 9), (2, "/host:metadata"), *entries, stat)))


# Device, ns: module jit_solve_fn(1) [0,60) runs a while [5,55) around
# fusion.1 [10,30) (s2v.embed), fusion.2 [30,40) (env.commit), reduce.1
# [40,50) (env.select) and fusion.3 [50,54) (a look-alike name, no scope),
# then fusion.4 [55,58), made without an op_name, whose fused computation
# holds an env.commit instruction below its root; module jit_other(2) [70,110) runs its own reduce.1 [75,85) and reduce.2
# [100,110), after the window.  Host: bench.window [0,100); bench.solve
# [0,60) holding solve.prepare [0,4), solve.dispatch [4,56) and
# solve.fetch [56,70); a second bench.solve [70,100) with no solve span.
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 60000 }
    events { metadata_id: 2 offset_ps: 70000 duration_ps: 40000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 5000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 5 offset_ps: 30000 duration_ps: 10000 }
    events { metadata_id: 6 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 7 offset_ps: 50000 duration_ps: 4000 }
    events { metadata_id: 9 offset_ps: 55000 duration_ps: 3000 }
    events { metadata_id: 6 offset_ps: 75000 duration_ps: 10000 }
    events { metadata_id: 8 offset_ps: 100000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_solve_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_other(2)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 6 value { id: 6 name: "%reduce.1 = f32[] reduce(f32[8]{0} %p, f32[] %z)" } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 8 value { id: 8 name: "%reduce.2 = f32[] reduce(f32[8]{0} %p, f32[] %z)" } }
  event_metadata { key: 9 value { id: 9 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 60000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000 }
    events { metadata_id: 4 offset_ps: 4000 duration_ps: 52000 }
    events { metadata_id: 5 offset_ps: 56000 duration_ps: 14000 }
    events { metadata_id: 2 offset_ps: 70000 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "solve.prepare" } }
  event_metadata { key: 4 value { id: 4 name: "solve.dispatch" } }
  event_metadata { key: 5 value { id: 5 name: "solve.fetch" } }
}
'''
SOLVE_HLO = {"while.1": "jit(solve_fn)/while",
             "fusion.1": "jit(solve_fn)/while/body/s2v.embed/jit(relu)/max",
             "fusion.2": "jit(solve_fn)/while/body/env.commit/mul",
             "reduce.1": "jit(solve_fn)/while/body/env.select/reduce_sum",
             "fusion.3": "jit(solve_fn)/while/body/s2v.embedded/mul",
             "fusion.4": ("", (2,))}
FUSED_HLO = {"param.1": "",
             "transpose.1": "jit(solve_fn)/while/body/env.commit/mul",
             "scatter.1": ""}
OTHER_HLO = {"reduce.1": "jit(other)/reduce_sum",
             "reduce.2": "jit(other)/reduce_sum"}
MODULES = {"jit_solve_fn(1)": {1: SOLVE_HLO, 2: FUSED_HLO},
           "jit_other(2)": {1: OTHER_HLO}}


@pytest.fixture
def synthetic(tmp_path):
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC)
                     + _metadata_plane(MODULES))
    return path


def test_the_embedded_hlo_gives_each_module_its_op_names(synthetic):
    """An instruction made without an op_name takes the first one found
    walking the computation it calls back from its root."""
    tables = scopes.module_tables(str(synthetic))
    commit = "jit(solve_fn)/while/body/env.commit/mul"
    assert tables == {"jit_solve_fn(1)": {**SOLVE_HLO, "fusion.4": commit,
                                          **FUSED_HLO},
                      "jit_other(2)": OTHER_HLO}


def test_self_time_is_summed_per_scope_within_the_window(synthetic):
    s = scopes.join(str(synthetic))
    ns = 1e-9
    assert s.seconds("s2v.embed") == pytest.approx(20 * ns)
    assert s.seconds("q.head") == 0.0
    assert s.seconds("env.select") == pytest.approx(10 * ns)
    assert s.seconds("env.commit") == pytest.approx(13 * ns)
    # the while's own 6 ns (50 less the 44 of its body), the look-alike
    # 4 ns and the other module's reduce.1 (named like the solve module's)
    # 10 ns; reduce.2 lies after the window
    assert s.seconds(None) == pytest.approx(20 * ns)
    assert s.seconds(None, "jit_other") == pytest.approx(10 * ns)
    assert s.coverage() == pytest.approx(43 / 53)
    assert (len(s.ops), s.matched) == (7, 7)


def test_a_scope_counts_only_as_a_whole_path_component():
    assert scopes.scope_of("jit(f)/while/body/env.commit/mul") == "env.commit"
    assert scopes.scope_of("jit(f)/while/body/s2v.embedded/mul") is None
    assert scopes.scope_of("jit(f)/while/body/env.commit.x/mul") is None
    assert scopes.scope_of("") is None


def test_idle_gaps_carry_the_innermost_solve_span(synthetic):
    # gaps [0,5) [58,75) [85,100); middles 2.5, 66.5 and 92.5
    assert scopes.join(str(synthetic)).gaps == [
        ("solve.fetch", pytest.approx(17e-9)),
        ("bench.solve", pytest.approx(15e-9)),
        ("solve.prepare", pytest.approx(5e-9))]


def _ctx(path, tmp_path, cell="a-cell", evals=2):
    """A reader's context whose cell has ``path`` as its trace, where
    ``run.py`` would leave it under a checkout's ``.traces/<cell>``."""
    traces = tmp_path / "root" / ".traces" / cell / "plugins"
    traces.mkdir(parents=True)
    shutil.copy(path, traces / "x.xplane.pb")
    window = Window(metrics={}, attempted=1, seconds=1.0,
                    counts={"evals": evals, "nodes": 8, "edges": 8})
    ctx = trace.Context(trace=trace.reduce(str(path)), window=window,
                        cell={"name": cell}, config={}, peak={})
    return ctx, tmp_path / "root"


def _read(metric, ctx, root):
    reader = harness.load_module(ROOT / "metrics" / f"{metric}.py")
    reader.ROOT = root
    return reader.read(ctx)


def test_the_readers_divide_by_the_window_evaluations(synthetic, tmp_path):
    ctx, root = _ctx(synthetic, tmp_path)
    assert _read("s2v_ms.infer", ctx, root) == pytest.approx(20e-9 * 1e3 / 2)
    assert _read("commit_ms.infer", ctx, root) == pytest.approx(13e-9 * 1e3
                                                                / 2)


def test_a_reader_without_a_trace_reads_nothing(tmp_path):
    reader = harness.load_module(ROOT / "metrics" / "s2v_ms.infer.py")
    reader.ROOT = tmp_path
    window = Window(metrics={}, attempted=1, seconds=1.0,
                    counts={"evals": 2})
    ctx = trace.Context(trace=None, window=window, cell={"name": "x"},
                        config={}, peak={})
    assert reader.read(ctx) is None


# -- the recorded chip traces --------------------------------------------------

def _instruction_names(path) -> dict:
    """Distinct instruction names of the device's op events."""
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    names.update(re.match(r"%([\w.\-]+)", e.name).group(1)
                                 for e in line.events)
    return names


def test_the_join_on_the_trace_recorded_before_the_scopes(tmp_path):
    """The dense N=1,024 chip trace recorded before the program had scopes
    (``dense_solve.xplane.pb``): 46 of the 50 instruction names
    are the solve module's, the other 4 are found in the small eager
    modules they ran in; no op is under a scope, so the readers read
    nothing."""
    path = FIXTURES / "dense_solve.xplane.pb"
    tables = scopes.module_tables(str(path))
    solve = next(t for m, t in tables.items() if m.startswith("jit_solve_fn"))
    names = _instruction_names(path)
    assert (len(names), len(names & set(solve))) == (50, 46)
    s = scopes.join(str(path))
    assert s.matched == len(s.ops) == 52
    assert s.coverage() == 0
    assert all(s.seconds(scope) == 0 for scope in scopes.SCOPES)
    assert {label for label, _ in s.gaps} == {"bench.solve"}
    ctx, root = _ctx(path, tmp_path, evals=4)
    assert _read("s2v_ms.infer", ctx, root) is None
    assert _read("commit_ms.infer", ctx, root) is None


def _hand_sum(path, scope) -> float:
    """Seconds of the solve module's op events inside bench.window whose
    instruction's op_name holds ``scope``; in these small solves every such
    event is a leaf, so its self time is its duration."""
    tables = scopes.module_tables(str(path))
    solve = next(t for m, t in tables.items() if m.startswith("jit_solve_fn"))
    lo = hi = None
    total = 0
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == trace.HOST_PLANE and e.name == \
                        trace.WINDOW_SPAN:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
                elif plane.name.startswith(trace.DEVICE_PREFIX) and \
                        line.name == trace.OPS_LINE:
                    events.append(e)
    for e in events:
        name = re.match(r"%([\w.\-]+)", e.name).group(1)
        if f"/{scope}/" in solve.get(name, "") and lo <= e.start_ns \
                and e.start_ns + e.duration_ns <= hi:
            total += e.duration_ns
    return total * 1e-9


def test_the_readers_on_a_trace_of_the_scoped_program(tmp_path):
    """The same two dense N=1,024 solves of two evaluations each, recorded
    on one TPU v5e chip with the program's scopes and host spans."""
    path = FIXTURES / "dense_scoped_solve.xplane.pb"
    s = scopes.join(str(path))
    assert s.matched == len(s.ops) == 52
    assert s.seconds("s2v.embed") == pytest.approx(5.9219e-05)
    assert s.seconds("env.commit") == pytest.approx(1.2006e-05)
    ctx, root = _ctx(path, tmp_path, evals=4)
    for scope, metric in (("s2v.embed", "s2v_ms.infer"),
                          ("env.commit", "commit_ms.infer")):
        assert _read(metric, ctx, root) == pytest.approx(
            _hand_sum(path, scope) * 1e3 / 4)


def test_the_gaps_of_the_scoped_trace_carry_the_solve_spans():
    """Every idle gap of the window is labelled, by a solve span where the
    host was inside one, else by the benchmark's bench.solve; the gaps add
    up to the window's idle time."""
    path = FIXTURES / "dense_scoped_solve.xplane.pb"
    gaps = scopes.join(str(path)).gaps
    assert {label for label, _ in gaps} == {
        "solve.prepare", "solve.dispatch", "solve.fetch", "bench.solve"}
    long = [label for label, s in gaps if s >= 5e-4]
    assert sorted(set(long)) == ["bench.solve", "solve.dispatch",
                                 "solve.prepare"]
    r = trace.reduce(str(path))
    assert sum(s for _, s in gaps) == pytest.approx(r.window_s - r.busy_s)
    assert {label for label, _ in r.gaps} == {"bench.solve"}
