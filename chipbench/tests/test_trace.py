"""The trace reduction: busy union, time per operation, idle gaps labelled
by the benchmark's host spans, and the readers that use them."""
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import harness, kernels, trace
from chipbench.harness import Window

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[1]

# one device, ns: a while [5,95) around ops [10,30) [30,40) [60,70), and an
# op [96,100); host spans: bench.window [0,100), bench.solve [0,50) and
# [55,100), bench.fetch [40,55)
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 5000 duration_ps: 90000 }
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 10000 }
    events { metadata_id: 1 offset_ps: 60000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 96000 duration_ps: 4000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%LONG%" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "jit_solve" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 3 offset_ps: 40000 duration_ps: 15000 }
    events { metadata_id: 2 offset_ps: 55000 duration_ps: 45000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fetch" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(solve)" } }
}
'''
LONG = ("%my_kernel.1 = f32[1,32,256]{2,1,0:T(8,128)} custom-call("
        "f32[32,32]{1,0} %a, f32[1,32,256]{2,1,0} %b, bf16[1,256,256]{2,1,0} "
        "%c, f32[1,32,256]{2,1,0} %d), custom_call_target=\"tpu_custom_call\""
        ", operand_layout_constraints={f32[32,32]{1,0}, f32[1,32,256]{2,1,0}}")


@pytest.fixture
def synthetic(tmp_path):
    text = SYNTHETIC.replace("%LONG%", LONG.replace('"', '\\"'))
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce(str(path))


def test_busy_is_the_union_of_device_ops_in_the_window(synthetic):
    assert synthetic.window_s == pytest.approx(100e-9)
    assert synthetic.busy_s == pytest.approx((90 + 4) * 1e-9)


def test_time_per_op_is_self_time_summed_over_events(synthetic):
    ops = {trace.short_name(n): o for n, o in synthetic.ops.items()}
    assert set(ops) == {"%my_kernel.1 custom-call", "%fusion.2 fusion",
                        "%while.1 while"}
    assert ops["%my_kernel.1 custom-call"].seconds == pytest.approx(30e-9)
    assert ops["%my_kernel.1 custom-call"].count == 2
    assert ops["%fusion.2 fusion"].seconds == pytest.approx(14e-9)
    # the while's own time: 90 ns less the 40 ns of operations inside it
    assert ops["%while.1 while"].seconds == pytest.approx(50e-9)


def test_idle_gaps_carry_the_innermost_host_span(synthetic):
    # gaps [0,5) [95,96); middles 2.5 and 95.5
    assert synthetic.gaps == [("bench.solve", pytest.approx(5e-9)),
                              ("bench.solve", pytest.approx(1e-9))]
    b = synthetic.breakdown()
    assert b["device_ops"][0][0] == "%while.1 while"
    assert len(b["idle_gaps"]) == 2


def test_idle_gaps_are_labelled_by_what_the_host_did(tmp_path):
    # ops [10,30) and [60,70); the gap [30,60) has its middle in bench.fetch
    text = SYNTHETIC.replace("%LONG%", "%op.1 = f32[1]{0} add(f32[1]{0} %a)")
    text = text.replace(
        "events { metadata_id: 4 offset_ps: 5000 duration_ps: 90000 }", "")
    text = text.replace(
        "events { metadata_id: 2 offset_ps: 30000 duration_ps: 10000 }", "")
    text = text.replace(
        "events { metadata_id: 2 offset_ps: 96000 duration_ps: 4000 }", "")
    text = text.replace("offset_ps: 40000 duration_ps: 15000",
                        "offset_ps: 40000 duration_ps: 20000")
    path = tmp_path / "gaps.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    r = trace.reduce(str(path))
    assert r.gaps == [("bench.fetch", pytest.approx(30e-9)),
                      ("bench.solve", pytest.approx(30e-9)),
                      ("bench.solve", pytest.approx(10e-9))]


def _ctx(reduced, **counts):
    peak = harness.load_json(ROOT / "peaks.json")["TPU v5 lite"]
    window = Window(metrics={}, attempted=1, seconds=2.0, counts=counts)
    config = {"embed_dim": 2, "num_layers": 2}
    return trace.Context(trace=reduced, window=window, cell={},
                         config=config, peak=peak)


def test_idle_share_reader(synthetic):
    reader = harness.load_module(ROOT / "metrics" / "idle_share.infer.py")
    assert reader.read(_ctx(synthetic)) == pytest.approx(6.0)


def test_mfu_counts_the_work_the_model_needs():
    reader = harness.load_module(ROOT / "metrics" / "mfu.infer.py")
    # N=4 nodes, E=6 directed edges, K=2, L=2: one aggregation 2*K*E=24,
    # theta3 and theta4 2*K*K*N*2=64, head theta6 32 + theta7 32 + theta5 8
    assert reader.flops_per_eval(4, 6, 2, 2) == 24 + 64 + 32 + 32 + 8
    ctx = _ctx(None, evals=10, nodes=4, edges=6)
    want = 100.0 * 160 * 10 / (2.0 * 197e12)
    assert reader.read(ctx) == pytest.approx(want)


def test_kernel_work_is_read_from_the_recorded_shapes(synthetic):
    shapes = kernels.shapes_of(LONG)
    assert shapes == [("f32", (1, 32, 256)), ("f32", (32, 32)),
                      ("f32", (1, 32, 256)), ("bf16", (1, 256, 256)),
                      ("f32", (1, 32, 256))]
    reader = harness.load_module(
        ROOT / "metrics" / "dense_fused_roofline.infer.py")
    flops, nbytes = reader.work(shapes)
    assert flops == 2 * 32 * 256 * 256 + 2 * 32 * 32 * 256
    # output, embed and base in f32, theta4, the bf16 adjacency
    assert nbytes == 3 * 32 * 256 * 4 + 32 * 32 * 4 + 256 * 256 * 2
    share = kernels.roofline_share(_ctx(synthetic), ("%my_kernel.",),
                                   reader.work)
    least = max(flops / 197e12, nbytes / 819e9)
    assert share == pytest.approx(100.0 * 2 * least / 30e-9)
    assert kernels.roofline_share(_ctx(synthetic), ("absent",),
                                  reader.work) is None


def test_a_recorded_tpu_trace():
    """Two solves of a dense ER N=1,024 graph, two evaluations each,
    recorded on one TPU v5e chip with the benchmark's host spans."""
    r = trace.reduce(str(FIXTURES / "dense_solve.xplane.pb"))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.010579889)
    assert 0 < r.busy_s < r.window_s
    kernel = [o for n, o in r.ops.items()
              if n.startswith("%fused_s2v_layer.")]
    assert len(kernel) == 1 and kernel[0].count == 4
    assert trace.short_name(max(r.ops, key=lambda n: r.ops[n].seconds)) \
        == "%fused_s2v_layer.3 custom-call"
    assert r.gaps[0][0] == "bench.solve"
    b = r.breakdown()
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    reader = harness.load_module(
        ROOT / "metrics" / "dense_fused_roofline.infer.py")
    share = reader.read(_ctx(r))
    assert 0 < share <= 100
    idle = harness.load_module(ROOT / "metrics" / "idle_share.infer.py")
    assert idle.read(_ctx(r)) == pytest.approx(
        100 * (1 - r.busy_s / r.window_s))


CSR_KERNEL = (
    "%fused_s2v_layer_csr.6 = f32[1,62,32,256]{3,2,1,0:T(8,128)S(1)} "
    "custom-call(f32[32,32]{1,0:T(8,128)S(1)} %a, s32[1,1,312576]{2,1,0} %b,"
    " s32[1,1,312576]{2,1,0} %c, f32[1,1,312576]{2,1,0} %d, "
    "f32[1,62,32,256]{3,2,1,0} %e, f32[1,62,32,256]{3,2,1,0} %f), "
    "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{f32[32,32]{1,0}}")


def test_csr_kernel_work_counts_the_edges_not_the_one_hot_matmuls():
    reader = harness.load_module(
        ROOT / "metrics" / "csr_fused_roofline.infer.py")
    flops, nbytes = reader.work(kernels.shapes_of(CSR_KERNEL))
    e, n, k = 312576, 62 * 256, 32
    assert flops == 2 * k * e + 2 * k * k * n
    assert nbytes == 32 * 32 * 4 + 3 * e * 4 + 3 * k * n * 4
    assert not CSR_KERNEL.startswith(
        harness.load_module(ROOT / "metrics" /
                            "dense_fused_roofline.infer.py").NAMES)


def test_a_kernel_name_without_operand_shapes_reads_nothing(tmp_path):
    text = SYNTHETIC.replace(
        "%LONG%", "%my_kernel.1 = f32[8]{0} custom-call(%a, %b), "
        "custom_call_target=\\\"tpu_custom_call\\\"")
    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    reader = harness.load_module(
        ROOT / "metrics" / "dense_fused_roofline.infer.py")
    ctx = _ctx(trace.reduce(str(path)))
    assert kernels.roofline_share(ctx, ("%my_kernel.",), reader.work) is None
