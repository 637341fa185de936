"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/tests/record_trace.py``): one plain and one fused level
segment-reduction kernel call between the benchmark's host spans."""

from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "segment_reduce.xplane.pb"
KERNELS = ("segment_aggregate_", "level_segment_aggregate_")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE), KERNELS)


def test_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the two kernels take nearly all of the device's busy time
    kernel_s = sum(c["seconds"] for c in reduced["calls"])
    assert 0.95 * reduced["busy_s"] < kernel_s <= reduced["busy_s"]


def test_kernel_calls_named_and_sized_from_their_shapes(reduced):
    calls = {c["name"]: c for c in reduced["calls"]}
    assert set(calls) == {"segment_aggregate_sum", "level_segment_aggregate_sum"}
    n, g, v = 1 << 16, 256, 2
    # codes (1, N) int32, values (V, N) float32, result (G, V) float32
    assert calls["segment_aggregate_sum"]["bytes"] == 4 * n + 4 * v * n + 4 * g * v
    assert calls["segment_aggregate_sum"]["kernel"] == "segment_aggregate_"
    # the fused launch: four block tables of 80, padded rows (N + N/2), 384 segments
    rows = n + n // 2
    assert calls["level_segment_aggregate_sum"]["bytes"] == 4 * 4 * 80 + 4 * rows + 4 * v * rows \
        + 4 * 384 * v
    assert calls["level_segment_aggregate_sum"]["kernel"] == "level_segment_aggregate_"
    assert all(c["seconds"] > 0 for c in calls.values())


def test_idle_gaps_attributed_to_host_spans(reduced):
    gaps = reduced["gaps"]
    assert gaps["bench.wait"] > 0.015  # the 20 ms sleep between the two calls
    total = sum(gaps.values())
    assert total == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


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
