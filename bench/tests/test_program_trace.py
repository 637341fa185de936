"""The program's own scopes and spans read back from profiler traces.

Two traces recorded on a TPU v5e: ``segment_reduce.xplane.pb``
(``record_trace.py``: kernels called outside any program scope, between the
benchmark's spans) and ``program_spans.xplane.pb``
(``record_program_trace.py``: a sparse plan on the Pallas kernel and one on
the XLA fallback, under the program's spans).  And a served step profiled
here on the CPU, which has host spans and no device plane.
"""

from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from bench import generator, harness, program_trace, trace_reduce
from bench.tests import tiny

DATA = Path(__file__).parent / "data"
OLD = DATA / "segment_reduce.xplane.pb"
NEW = DATA / "program_spans.xplane.pb"


@pytest.fixture(scope="module")
def old():
    return trace_reduce.reduce(str(OLD)), program_trace.reduce(str(OLD))


@pytest.fixture(scope="module")
def new():
    return trace_reduce.reduce(str(NEW)), program_trace.reduce(str(NEW))


@pytest.mark.parametrize("path", [OLD, NEW], ids=["old", "new"])
def test_wire_reader_gives_every_device_event_in_file_order(path):
    raw = program_trace.tf_ops(str(path))
    data = ProfileData.from_file(str(path))
    planes = {p.name: p for p in data.planes if p.name in raw}
    assert planes and set(planes) == set(raw)
    for name, plane in planes.items():
        events = next(ln for ln in plane.lines if ln.name == "XLA Ops").events
        assert [n for n, _ in raw[name]] == [ev.name for ev in events]
        # the kernel's own event and the copy XLA adds after it carry its path
        ops = [op for n, op in raw[name] if op and "segment_aggregate_sum" in n + op]
        assert any("pallas_call" in op for op in ops)


def test_a_program_without_scopes_or_spans_reads_as_before(old):
    before, after = old
    assert set(after["scopes"]) == {program_trace.UNSCOPED}
    assert after["scopes"][program_trace.UNSCOPED] == pytest.approx(sum(before["ops"].values()))
    assert after["spans"] == {} and after["reduce_bytes"] == {}
    assert set(before["gaps"]) == {"bench.wait"}  # named by the benchmark's spans alone


def test_old_fixture_reduces_to_the_fields_and_values_it_gave(old):
    before, _ = old
    assert set(before) == {"window_s", "busy_s", "profile_s", "edges", "devices", "ops", "gaps"}
    assert before["devices"] == 1
    assert {"segment_aggregate_sum", "level_segment_aggregate_sum"} <= set(before["ops"])
    # its idle time outside any span lay before the first and after the last
    # device operation: the profile's edges, now apart from the gaps
    assert set(before["gaps"]) == {"bench.wait"}
    assert before["edges"]["start"] > 0 and before["edges"]["stop"] > 0
    assert sum(before["gaps"].values()) == pytest.approx(
        before["window_s"] - before["busy_s"], rel=1e-6)


def test_both_reductions_land_in_their_scope_copies_included(new):
    before, after = new
    raw = next(iter(program_trace.tf_ops(str(NEW)).values()))
    # the kernel, every operation XLA adds after it (they carry its path),
    # and the XLA reduction of the fallback plan
    kernel_ops = [(n, op) for n, op in raw if op and "segment_aggregate_sum/" in op]
    fallback_ops = [(n, op) for n, op in raw if op and "scatter" in op]
    assert len(kernel_ops) >= 2 and fallback_ops
    assert any(trace_reduce.op_label(n).startswith("copy") for n, _ in kernel_ops)
    for _, op in kernel_ops + fallback_ops:
        assert program_trace.scope_of(op) == "segment_reduce_sum", op
    assert "segment_aggregate_sum" in after["scope_ops"]["segment_reduce_sum"]
    assert after["scopes"]["segment_reduce_sum"] > before["ops"]["segment_aggregate_sum"]
    assert after["scopes"]["rowwise"] > 0
    # every device second is under some scope or named unscoped, and the
    # plans' own stages hold nearly all of it
    assert sum(after["scopes"].values()) == pytest.approx(sum(before["ops"].values()))
    assert after["scopes"].get(program_trace.UNSCOPED, 0.0) < 0.1 * before["busy_s"]


def test_idle_gap_goes_to_the_innermost_program_span(new):
    before, after = new
    # the benchmark's own spans and the program's name the gaps by one rule
    assert before["gaps"]["treant.session.derive"] > 0.015  # the 20 ms sleep
    assert "serve.step" not in before["gaps"]  # the program's spans lie inside it
    spans = after["spans"]
    assert spans["treant.plans.run"]["count"] == 2
    assert spans["treant.serve.step"]["count"] == 1
    assert spans["treant.serve.step"]["seconds"] >= spans["treant.session.derive"]["seconds"]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(sparse_plan)/rowwise/gather:gather", "rowwise"),
    ("jit(sparse_batch_plan)/vmap(rowwise)/mul:mul", "rowwise"),
    ("jit(level_plan)/segment_reduce_max/jit(aggregate_op)/segment_aggregate_max/pallas_call:",
     "segment_reduce_max"),
    ("jit(sparse_plan)/row_blocks/while/body/closed_call/finalize/transpose:transpose",
     "finalize"),
    ("jit(sparse_plan)/row_blocks/reshape:reshape", "row_blocks"),
    ("jit(cube_slice)/cube_slice/select_n:select", "cube_slice"),
    ("jit(<lambda>)/jit(aggregate_op)/segment_aggregate_sum/pallas_call:", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_takes_the_innermost_scope(tf_op, scope):
    assert program_trace.scope_of(tf_op) == scope


def test_span_names_lose_their_ids():
    assert trace_reduce.span_name("treant.serve.step#batch=3,events=16#") == "treant.serve.step"
    assert trace_reduce.span_name("treant.plans.run") == "treant.plans.run"


# -- the readers, on hand-made reductions -------------------------------------------------
def _reader(name):
    return tiny.load("taxi-7m.jump-16")["readers"][name][1]


def _w(counters=None, traced_events=4):
    return {"counters": counters or {}, "events": 10, "compiles": 0, "rerendered": 30,
            "trace": {"busy_s": 1.0}, "traced_events": traced_events, "peaks": None}


PROGRAM = {"scopes": {"rowwise": 0.2, "segment_reduce_sum": 0.05, "segment_reduce_max": 0.01,
                      "finalize": 0.001, "unscoped": 0.01},
           "spans": {"treant.session.derive": {"seconds": 0.008, "count": 16}},
           "scope_ops": {}, "reduce_bytes": {"segment_reduce_sum": 12e9, "segment_reduce_max": 3e9}}
PARENT = {"scopes": {"unscoped": 0.3}, "spans": {}, "scope_ops": {}, "reduce_bytes": {}}


@pytest.mark.parametrize("name,program,parent", [
    ("rowwise_ms_per_event", 50.0, None),
    ("segment_reduce_scoped_ms_per_event", 15.0, None),
    ("derive_ms_per_event", 2.0, None),
])
def test_trace_readers(monkeypatch, name, program, parent):
    read = _reader(name).read
    monkeypatch.setattr(program_trace, "read", lambda w: PROGRAM)
    assert read(_w()) == pytest.approx(program)
    assert read(_w(traced_events=0)) is None
    monkeypatch.setattr(program_trace, "read", lambda w: PARENT)
    assert read(_w()) is parent
    monkeypatch.setattr(program_trace, "read", lambda w: None)
    assert read(_w()) is None


def test_roofline_reader_takes_bytes_and_seconds_under_the_reduction_scopes(monkeypatch):
    read = _reader("segment_reduce_roofline_pct").read
    w = dict(_w(), peaks={"hbm_bytes_per_s": 1e12})
    monkeypatch.setattr(program_trace, "read", lambda w: PROGRAM)
    # 15 GB at 1 TB/s take 15 ms at least; the scopes took 60 ms
    assert read(w) == pytest.approx(25.0)
    for program in (PARENT, dict(PROGRAM, reduce_bytes={})):  # nothing to read
        monkeypatch.setattr(program_trace, "read", lambda w, p=program: p)
        assert read(w) is None
    monkeypatch.setattr(program_trace, "read", lambda w: None)
    assert read(w) is None


def test_queue_reader_reads_the_counter_and_nothing_without_it():
    read = _reader("serve_queue_ms_per_event").read
    assert read(_w({"serve.queue_wait_s": 3.2, "serve.events_processed": 16})) == \
        pytest.approx(200.0)
    assert read(_w({"serve.events_processed": 16})) is None  # a program without it
    assert read(_w({"serve.queue_wait_s": 0.0, "serve.events_processed": 0})) is None


def test_trace_readers_read_nothing_from_an_untraced_run():
    w = dict(_w(), trace=None)
    for name in ("rowwise_ms_per_event", "segment_reduce_scoped_ms_per_event",
                 "derive_ms_per_event", "segment_reduce_roofline_pct"):
        assert _reader(name).read(w) is None


# -- a served step, profiled on the CPU ---------------------------------------------------
def _contains(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_served_step_and_idle_spans_nest(tmp_path):
    cell = tiny.cell("taxi-7m.jump-16")
    cfg, mix = cell["config"], cell["mix"]
    system = harness.build(cfg, 5, 2, tiny.SCALE)
    gens = generator.analysts(mix, cfg, 5, generator.WINDOW_STREAM)

    def one_step():
        for handle, gen in zip(system.handles, gens):
            handle.submit(harness.to_event(gen.next().event))
        assert system.server.step() == 2
        jax.block_until_ready([r.factor.field for h in system.handles
                               for r in h.last_result.results.values()])

    one_step()  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        one_step()
        system.server.idle()
    data = ProfileData.from_file(trace_reduce.find(str(tmp_path)))
    spans = [s for s in trace_reduce.host_spans(data) if s[0].startswith("treant.")]
    steps = [s for s in spans if s[0] == "treant.serve.step"]
    assert len(steps) == 1
    names = {s[0] for s in spans if _contains(steps[0], s)}
    assert {"treant.serve.record", "treant.serve.fan_out", "treant.session.derive",
            "treant.serve.probe", "treant.engine.execute", "treant.engine.messages",
            "treant.plans.run", "treant.serve.wait", "treant.serve.distribute"} <= names
    executes = [s for s in spans if s[0] == "treant.engine.execute"]
    assert all(any(_contains(e, r) for e in executes)
               for r in spans if r[0] == "treant.plans.run" and _contains(steps[0], r))
    idle = [s for s in spans if s[0] == "treant.serve.idle"]
    assert len(idle) == 1 and not _contains(steps[0], idle[0])
    assert {"treant.scheduler.run", "treant.policy.extras"} <= {
        s[0] for s in spans if _contains(idle[0], s)}
    reduced = program_trace.reduce(trace_reduce.find(str(tmp_path)))
    assert reduced["spans"]["treant.session.derive"]["count"] == 2  # one per session
    assert reduced["scopes"] == {}  # no TPU plane on the CPU
