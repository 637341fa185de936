"""The trace reduction on small traces recorded on a TPU v5e:
``segment_reduce.xplane.pb`` (``bench/tests/record_trace.py``: one plain and
one fused level segment-reduction kernel call between the benchmark's host
spans) and ``program_spans.xplane.pb`` (``record_program_trace.py``: a plan
on the Pallas kernel and one on XLA's reduction, under the program's spans)."""

import importlib.util
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import program_trace, trace_reduce

DATA = Path(__file__).parent / "data"
TRACE = DATA / "segment_reduce.xplane.pb"
PROGRAM = DATA / "program_spans.xplane.pb"
N, G, V = 1 << 16, 256, 2


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE))


def _reductions(path):
    """``(label, tf_op, reduction bytes, HLO bytes)`` of each operation
    that reduces rows."""
    return [(trace_reduce.op_label(name), op, moved, trace_reduce.hlo_bytes(name))
            for events in program_trace.tf_ops(str(path)).values() for name, op in events
            if (moved := trace_reduce.reduction_bytes(name, op)) is not None]


def test_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the two kernels take nearly all of the device's busy time
    kernel_s = sum(s for k, s in reduced["ops"].items() if "segment_aggregate_" in k)
    assert 0.95 * reduced["busy_s"] < kernel_s <= reduced["busy_s"]


@pytest.mark.parametrize("path", [TRACE, PROGRAM], ids=["kernels", "program"])
def test_window_runs_from_the_first_device_operation_to_the_last(path):
    reduced = trace_reduce.reduce(str(path))
    edges = reduced["edges"]
    # the profiler's start and stop latency: idle, reported apart
    assert edges["start"] > 0 and edges["stop"] > 0
    assert reduced["window_s"] + edges["start"] + edges["stop"] == \
        pytest.approx(reduced["profile_s"], rel=1e-9)
    events = next(iter(trace_reduce.device_events(ProfileData.from_file(str(path))).values()))
    first = min(ev.start_ns for ev in events)
    last = max(ev.start_ns + ev.duration_ns for ev in events)
    assert reduced["window_s"] == pytest.approx((last - first) / 1e9, rel=1e-9)
    # the idle share leaves the edges out
    spec = importlib.util.spec_from_file_location(
        "device_idle_pct", Path(__file__).parents[1] / "metrics" / "device_idle_pct.py")
    idle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(idle)
    pct = idle.read({"trace": reduced})
    assert pct == pytest.approx(100 * sum(reduced["gaps"].values()) / reduced["window_s"])
    assert pct < 100 * (1 - reduced["busy_s"] / reduced["profile_s"])


def test_kernel_calls_named_and_sized_from_their_shapes():
    found = {label: (moved, hlo) for label, _, moved, hlo in _reductions(TRACE)}
    assert set(found) == {"segment_aggregate_sum", "level_segment_aggregate_sum"}
    # codes (1, N) int32, values (V, N) float32, result (G, V) float32:
    # the kernel's own operands and result
    assert found["segment_aggregate_sum"] == (4 * N + 4 * V * N + 4 * G * V,) * 2
    # the fused launch: padded rows (N + N/2), 384 segments; its four block
    # tables of 80 are no rows and no result, so the reduction need not move them
    rows = N + N // 2
    moved = 4 * rows + 4 * V * rows + 4 * 384 * V
    assert found["level_segment_aggregate_sum"] == (moved, moved + 4 * 4 * 80)


def test_pallas_and_xla_reductions_count_the_same_bytes():
    found = _reductions(PROGRAM)
    assert [op.rsplit("/", 1)[-1] for _, op, _, _ in found] == ["pallas_call:", "scatter-add:"]
    (_, _, kernel, kernel_hlo), (_, _, xla, xla_hlo) = found
    assert kernel == xla == kernel_hlo == 4 * N + 4 * V * N + 4 * G * V
    assert xla_hlo != xla  # XLA fused the rowwise stage's inputs in
    assert program_trace.reduce(str(PROGRAM))["reduce_bytes"] == {"segment_reduce_sum": 2 * xla}


@pytest.mark.parametrize("text,path,moved", [
    # one member: codes (1, N), values (V, N), result (G, V)
    ("%segment_aggregate_sum.1 = f32[264,7]{1,0} custom-call(s32[1,1024]{1,0} %a, "
     "f32[7,1024]{1,0} %b), custom_call_target=\"tpu_custom_call\"",
     "jit(sparse_plan)/segment_reduce_sum/jit(aggregate_op)/segment_aggregate_sum/pallas_call:",
     4 * 1024 + 4 * 7 * 1024 + 4 * 264 * 7),
    # two members under vmap share their codes: values (2, V, N), result (2, G, V)
    ("%segment_aggregate_sum.1 = f32[2,8,7]{2,1,0} custom-call(s32[1,1024]{1,0} %a, "
     "f32[2,7,1024]{2,1,0} %b)",
     "jit(sparse_batch_plan)/vmap(segment_reduce_sum)/jit(aggregate_op)/"
     "segment_aggregate_sum/pallas_call:",
     4 * 1024 + 4 * 2 * 7 * 1024 + 4 * 2 * 8 * 7),
    # XLA with the rowwise stage fused in: only the rows and the result count
    ("%fusion.1 = f32[8,7]{1,0} fusion(s32[1024]{0} %codes, f32[1024]{0} %v, "
     "f32[265,7]{1,0} %table, s32[1024]{0} %idx), kind=kCustom",
     "jit(sparse_plan)/segment_reduce_max/scatter-max:", 4 * 1024 + 4 * 7 * 1024 + 4 * 8 * 7),
    # what the reduction's scope holds besides: a copy of its result, a slice
    # of it, a pad of its codes, a transpose of its values
    ("%copy.2 = f32[256,2]{0,1} copy(f32[256,2]{1,0} %segment_aggregate_sum.1)",
     "jit(sparse_plan)/segment_reduce_sum/jit(aggregate_op)/segment_aggregate_sum/pallas_call:",
     None),
    ("%slice.10 = f32[2,1,7]{2,1,0} slice(f32[2,8,7]{2,1,0} %x), slice={[0:2], [0:1], [0:7]}",
     "jit(sparse_batch_plan)/vmap(segment_reduce_sum)/jit(aggregate_op)/slice:", None),
    ("%pad.1 = s32[2048]{0} pad(s32[1000]{0} %c, s32[] %m), padding=0_1048",
     "jit(sparse_plan)/segment_reduce_sum/jit(aggregate_op)/pad:", None),
    ("%transpose.3 = f32[1024,7]{1,0} transpose(f32[7,1024]{1,0} %v), dimensions={1,0}",
     "jit(sparse_plan)/segment_reduce_mul/transpose:", None),
    ("no instruction here", "jit(f)/scatter-add:", None),
], ids=["pallas", "pallas-vmap", "xla-fused", "copy", "slice", "pad", "transpose", "no-hlo"])
def test_reduction_bytes_from_the_shapes_alone(text, path, moved):
    assert trace_reduce.reduction_bytes(text, path) == moved


def test_idle_gaps_attributed_to_host_spans(reduced):
    gaps = reduced["gaps"]
    assert gaps["bench.wait"] > 0.015  # the 20 ms sleep between the two calls
    total = sum(gaps.values())
    assert total == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_idle_gaps_named_by_the_programs_spans_without_their_ids():
    reduced = trace_reduce.reduce(str(PROGRAM))
    gaps = reduced["gaps"]
    assert gaps["treant.session.derive"] > 0.015  # the 20 ms sleep
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                               rel=1e-6)
    data = ProfileData.from_file(str(PROGRAM))
    host = trace_reduce.host_spans(data)
    assert {"treant.serve.step", "treant.plans.run", "serve.step"} <= {n for n, _, _ in host}
    assert all("#" not in name for name, _, _ in host)
    # a trace that keeps a span's ids in its name (``name#k=v#``) loses them
    assert trace_reduce.span_name("treant.plans.run#kind=sparse#") == "treant.plans.run"
    # a gap inside a plan's run goes to that span, named without its ids
    run = next((s, e) for name, s, e in host if name == "treant.plans.run")
    assert trace_reduce.gap_label(host, run[0], run[1]) == "treant.plans.run"
    assert trace_reduce.gap_label(host, -2.0, -1.0) == "none"


def test_breakdown_lists_at_most_ten_of_each(reduced):
    b = trace_reduce.breakdown(reduced)
    assert b["device_ops"][0][0] == "level_segment_aggregate_sum"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) for _, s in b["device_ops"] + b["idle_gaps"])


def test_hlo_text_without_attributes():
    text = ("%copy-start = (f32[65536,2]{0,1:T(2,128)S(1)}, u32[]{:S(2)}) "
            "copy-start(f32[65536,2]{0,1:T(2,128)} %v.1), frontend_attributes={a={}}")
    assert trace_reduce.hlo_call(text) == "(f32[65536,2], u32[]) copy-start(f32[65536,2] %v.1)"
    assert trace_reduce.hlo_bytes(text) == 2 * 65536 * 2 * 4 + 4
    assert trace_reduce.hlo_bytes("no instruction here") is None
    assert trace_reduce.op_label("%level_segment_aggregate_sum.12 = f32[8] x()") == \
        "level_segment_aggregate_sum"
