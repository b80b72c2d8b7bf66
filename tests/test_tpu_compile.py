"""Compile rehearsal on a described TPU v5e chip (no chip attached).

Every main-path S2V kernel is compiled with ``interpret=False`` at K=32
on the train shapes, on the paper's largest dense graph (W1, N=21,000)
and on the BA N=16,384 solve shapes, and must lower to a Mosaic kernel
(``tpu_custom_call``).  The size rule ``s2v_kernel_fits`` is held to the
compiler: at the largest shape it admits the kernel compiles, and just
past its bound the compiler refuses the kernel for VMEM.

The topology is described only inside the module fixture, never while
this file is imported, so pytest-xdist workers all collect the same
tests and only the worker given this file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.s2v import s2v_kernel_fits
from repro.kernels import ops, s2v_csr, s2v_fused, s2v_gather

K = 32
# (B, N, D, E) per shape; D and E are the BA d=4 / d=10 maxima from
# `graphs.barabasi_albert_edges` with seed 0.
SHAPES = {
    "train": dict(b=64, n=1024, d=174, e=8032),
    "w1": dict(b=1, n=21_000),
    "w1_sp4": dict(b=1, nl=5_250, n=21_000),
    "er64k_sp4": dict(b=1, nl=16_000, n=64_000),
    "ba16k": dict(b=1, n=16_384, d=1_089, e=326_208),
}
SHAPED = [("dense_fused", "train"), ("mp_aggregate", "train"),
          ("sparse_fused", "train"), ("gather", "train"),
          ("csr_fused", "train"), ("dense_fused", "w1"),
          ("mp_aggregate", "w1_sp4"), ("mp_aggregate", "er64k_sp4"),
          ("sparse_fused", "ba16k"),
          ("gather", "ba16k"), ("csr_fused", "ba16k")]
# every kernel in f32; bf16 where the kernel takes a compute dtype (the
# gather kernel is f32-only)
CASES = [(k, s, c) for k, s in SHAPED for c in ("f32", "bf16")
         if not (k == "gather" and c == "bf16")]


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2 host, with the persistent compilation cache off
    (entries written here cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler for this jax
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One device of the described v5e:2x2 host."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lower(sharding, kernel, cd=jnp.float32, *, b, n, d=0, e=0, nl=None):
    """Lower one kernel at the given shapes for the described chip."""
    s = lambda *shape, dt=jnp.float32: _spec(sharding, shape, dt)
    i32 = jnp.int32
    nl = n if nl is None else nl
    if kernel == "dense_fused":
        fn = lambda t, x, a, bb: s2v_fused.fused_s2v_layer(
            t, x, a, bb, compute_dtype=cd, interpret=False)
        args = (s(K, K), s(b, K, n), s(b, n, n), s(b, K, n))
    elif kernel == "mp_aggregate":
        fn = lambda x, a: s2v_fused.mp_aggregate(
            x, a, compute_dtype=cd, interpret=False)
        args = (s(b, K, nl), s(b, nl, n))
    elif kernel == "sparse_fused":
        fn = lambda t, x, nb, ed, bb: s2v_fused.fused_s2v_layer_sparse(
            t, x, nb, ed, bb, compute_dtype=cd, interpret=False)
        args = (s(K, K), s(b, K, n), s(b, n, d, dt=i32), s(b, n, d),
                s(b, K, n))
    elif kernel == "gather":
        fn = lambda x, nb, ed: s2v_gather.sparse_mp_aggregate(
            x, nb, ed, interpret=False)
        args = (s(b, K, n + 1), s(b, n, d, dt=i32), s(b, n, d))
    else:
        fn = lambda t, x, ix, r, w, bb: s2v_csr.fused_s2v_layer_csr(
            t, x, ix, r, w, bb, compute_dtype=cd, interpret=False)
        args = (s(K, K), s(b, K, n), s(b, e, dt=i32), s(b, e, dt=i32),
                s(b, e), s(b, K, n))
    return jax.jit(fn).lower(*args)


@pytest.mark.parametrize("kernel,shape,compute", CASES)
def test_kernel_compiles_for_v5e(one_chip, kernel, shape, compute):
    cd = {"f32": jnp.float32, "bf16": jnp.bfloat16}[compute]
    compiled = _lower(one_chip, kernel, cd, **SHAPES[shape]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _largest(fits, start, step):
    """Largest ``start + i·step`` that ``fits``."""
    assert fits(start)
    x = start
    while fits(x + step):
        x += step
    return x


# (kernel, rep, compute, which shape the bound is on)
BOUNDS = [("csr_fused", "csr", "f32", "n"),
          ("csr_fused", "csr", "bf16", "n"),
          ("sparse_fused", "sparse", "f32", "d"),
          ("gather", "sparse", "f32", "d")]


@pytest.mark.parametrize("kernel,rep,compute,axis", BOUNDS)
def test_size_rule_matches_compiler(one_chip, kernel, rep, compute, axis):
    """The rule admits the kernel up to its VMEM bound and the compiler
    accepts it there; just past it (by about half a MiB, twice the rule's
    reserve) the rule picks XLA and the compiler refuses the kernel for
    VMEM."""
    cd = {"f32": jnp.float32, "bf16": jnp.bfloat16}[compute]
    agg = kernel == "gather"
    if axis == "n":                       # CSR: whole (K, N) panels
        fits = lambda n: s2v_kernel_fits(rep, k=K, n=n, compute_dtype=cd)
        inside = _largest(fits, 256, 256)
        outside = inside + 2048
        shape = lambda v: dict(b=1, n=v, e=326_208)
    else:                                 # sparse: (D, TN) edge-list blocks
        fits = lambda d: s2v_kernel_fits(rep, k=K, max_degree=d,
                                         compute_dtype=cd,
                                         aggregate_only=agg)
        inside = _largest(fits, 8, 8)
        outside = inside + 256
        # N large enough that XLA cannot hold the (B, D, N) lists in VMEM
        shape = lambda v: dict(b=1, n=1024, d=v)
    assert not fits(outside)
    compiled = _lower(one_chip, kernel, cd, **shape(inside)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(Exception, match="vmem"):
        _lower(one_chip, kernel, cd, **shape(outside)).compile()


def test_dense_kernels_fit_at_every_n():
    """The dense kernels take the blocks that ``dense_tiles`` picks from
    K, N and Nl within a budget inside the limit, so the rule, counting
    those same blocks, admits them at the train, W1, W1-sp4 and
    ER-64k-sp4 shapes."""
    for shape in ("train", "w1", "w1_sp4", "er64k_sp4"):
        n = SHAPES[shape]["n"]
        nl = SHAPES[shape].get("nl", n)
        for cd in (jnp.float32, jnp.bfloat16):
            for agg in (False, True):
                assert s2v_kernel_fits("dense", k=K, n=n, nl=nl,
                                       compute_dtype=cd, aggregate_only=agg)


def test_w1_dense_kernel_reads_the_adjacency_unpadded(one_chip):
    """At W1 the dense layer is one custom call, named after the jit
    wrapper ``fused_s2v_layer`` (the roofline reader finds the kernel by
    that name and reads the work from its shapes), whose adjacency operand
    is the (1, N, N) array itself: the program holds no pad."""
    n = SHAPES["w1"]["n"]
    s = lambda *shape: _spec(one_chip, shape)
    text = ops.fused_s2v_layer.lower(
        s(K, K), s(1, K, n), s(1, n, n), s(1, K, n),
        interpret=False).compile().as_text()
    assert not re.search(r"\bpad\(", text)
    calls = re.findall(r"(%[\w.]+) = \S+ custom-call\(([^)]*)\)", text)
    assert len(calls) == 1
    name, operands = calls[0]
    assert name.startswith("%fused_s2v_layer.")
    adj = operands.split(", ")[2]
    shape = re.search(rf"^\s*{re.escape(adj)} = (\S+) ", text, re.M)
    assert shape and shape.group(1).startswith(f"f32[1,{n},{n}]")


def test_er64k_row_split_solve_holds_its_rows_only(v5e_2x2, monkeypatch):
    """The dense solve of the four-chip cell (ER N=64,000 on a (1, 4)
    mesh) compiled for the described v5e 2x2 host: each chip's arguments
    are its 16,000 adjacency rows (a quarter of the 16.4 GB graph) and the
    masks, the donated rows carry the loop in place, no array of the whole
    graph exists, and the one kernel, ``mp_aggregate``, reads the rows as
    they are: no copy or relayout of the rows anywhere."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import engine, s2v
    from repro.core.graphs import GraphState
    from repro.core.policy import PolicyConfig, init_policy
    from repro.kernels import backend
    n, sp = 64_000, 4
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(1, sp), ("data", "graph"))
    spec = lambda shape, ps, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, ps))
    params = jax.tree.map(lambda x: spec(x.shape, P(), x.dtype), init_policy(
        jax.random.key(0), PolicyConfig(embed_dim=K)))
    state = GraphState(adj=spec((1, n, n), P("data", "graph", None)),
                       candidate=spec((1, n), P("data")),
                       solution=spec((1, n), P("data")))
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(s2v, "on_tpu", lambda: True)
    monkeypatch.setattr(engine, "make_mesh", lambda dp, sp: mesh)
    engine._build_solve_step.cache_clear()
    try:
        fn = engine.get_solve_step(rep="dense", problem="mvc", num_layers=2,
                                   use_adaptive=True, spatial=(1, sp),
                                   max_d=8, donate=True)
        compiled = fn.lower(params, state,
                            spec((), P(), jnp.int32)).compile()
    finally:
        engine._build_solve_step.cache_clear()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    rows = 4 * n * n // sp
    assert rows <= mem.argument_size_in_bytes < rows + (1 << 20)
    assert mem.alias_size_in_bytes >= rows
    assert mem.temp_size_in_bytes < 16 << 20
    assert f"f32[1,{n},{n}]" not in text
    block = re.escape(f"f32[1,{n // sp},{n}]")
    assert not re.search(rf"= {block}\S* (copy|transpose|bitcast-convert)\(",
                         text)
    calls = re.findall(r"(%[\w.]+) = \S+ custom-call\(", text)
    assert len(calls) == 1 and calls[0].startswith("%mp_aggregate."), calls
