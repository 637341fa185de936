"""The server's think-time drain in units: bounded, exact, and read.

``TreantServer.idle`` advances the shared scheduler by one unit at a time,
at most one unit is ever on the device, and ``step`` waits for it before
dispatching.  Over a small flight-shaped star (``Flights`` ⋈ ``Carrier`` ⋈
``Airport`` ⋈ ``Dates``, integer measures so every ring is exact) with a SUM
and a MAX viz:

- scripted drags with ``idle()`` ticks between their steps serve every viz
  exactly as a cold ``use_plans=False`` execution of its query;
- after ``idle()`` returns, only the outstanding unit's outputs may still be
  running; an ``idle()`` that finds it running is back within a poll, and
  ``step`` records its wait in ``think_wait_s``;
- a unit after a brush on a ``Dates`` attribute puts the ``Flights→Dates``
  message of every re-rendered viz in the store first, and the drag's next
  step reads exactly those (``think_read``);
- a brush on another bag counts the think-written entries it made stale as
  ``think_dropped``;
- while a session may be back within twice its pace and its passes would
  not finish before it is expected, only their upward half (toward the
  brushed bag) runs, and ``idle`` sleeps a poll instead of dispatching;
- a level run under a one-row ``UnitBudget`` dispatches a single message
  per call and ends with the messages an unbudgeted pass computes.
"""

import time

import jax
import numpy as np
import pytest

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import DashboardSpec, Treant, VizSpec
from repro.core.dashboard import SetFilter
from repro.relational.relation import Catalog, Relation
from repro.serve import TreantServer

DOMS = {"carrier_id": 12, "airport_id": 20, "date_id": 60, "delay_bucket": 8,
        "carrier_group": 3, "airport_state": 5, "month": 4, "dow": 7}

SPEC = DashboardSpec(vizzes=(
    VizSpec("sum_by_state", measure=("Flights", "m"), ring="sum",
            group_by=("airport_state",)),
    VizSpec("max_by_month", measure=("Flights", "m"), ring="tropical_max",
            group_by=("month",)),
))
VIZZES = [v.name for v in SPEC.vizzes]


def flight_star(n: int = 600, seed: int = 0) -> Catalog:
    rng = np.random.default_rng(seed)

    def codes(attr, k):
        return rng.integers(0, DOMS[attr], k).astype(np.int32)

    fact = ("carrier_id", "airport_id", "date_id", "delay_bucket")
    days = np.arange(DOMS["date_id"], dtype=np.int32)
    return Catalog([
        Relation("Flights", fact, {a: codes(a, n) for a in fact}, DOMS,
                 measures={"m": rng.integers(0, 16, n).astype(np.float32)}),
        Relation("Carrier", ("carrier_id", "carrier_group"),
                 {"carrier_id": np.arange(DOMS["carrier_id"], dtype=np.int32),
                  "carrier_group": codes("carrier_group", DOMS["carrier_id"])}, DOMS),
        Relation("Airport", ("airport_id", "airport_state"),
                 {"airport_id": np.arange(DOMS["airport_id"], dtype=np.int32),
                  "airport_state": codes("airport_state", DOMS["airport_id"])}, DOMS),
        Relation("Dates", ("date_id", "month", "dow"),
                 {"date_id": days, "month": days // 15, "dow": days % 7}, DOMS),
    ])


def served():
    t = Treant(flight_star(), use_plans=True)
    server = TreantServer(t)
    return t, server, server.open_session(SPEC, name="a")


def brush(server, handle, attr, lo, hi):
    """Serve one brush.  A scripted brush follows the last at once, so its
    gaps are no analyst's pace: they are cleared, and no hold applies (the
    hold has its own test)."""
    handle.submit(SetFilter(attr, lo=lo, hi=hi))
    assert server.step() == 1
    handle._gaps.clear()


def store_counts(t):
    return dict(t.cache_stats()["store"])


def edge_sigs(t, handle, u, v):
    """Full store signatures of message ``u→v`` of each viz's current query."""
    out = set()
    for viz in VIZZES:
        q = handle.query_of(viz)
        eng = t.engine_for(q.ring_name, q.measure)
        base = eng.edge_sig(q, u, v, eng.place_predicates(q))
        out.add(t.store.full_sig(base, eng.gamma_carry(q, u, v)))
    return out


def cold(t, q):
    ref = Treant(Catalog([t.catalog.get(n) for n in t.catalog.names()]), use_plans=False)
    f, _ = ref.engine_for(q.ring_name, q.measure).execute(q)
    return f


def assert_exact(t, handle):
    for viz in VIZZES:
        q = handle.query_of(viz)
        res = handle.last_result.results.get(viz)
        got = res.factor if res is not None else handle.read(viz).factor
        want = cold(t, q)
        np.testing.assert_array_equal(
            np.asarray(got.project_to(q.group_by).field),
            np.asarray(want.project_to(q.group_by).field))


@pytest.mark.parametrize("ticks", [1, 3])
def test_drags_with_idle_ticks_serve_exactly_the_cold_answers(ticks):
    t, server, h = served()
    drags = [("dow", 1, 3), ("delay_bucket", 2, 5), ("month", 0, 2), ("carrier_group", 0, 1)]
    for attr, lo, hi in drags:
        for step in range(4):
            brush(server, h, attr, lo + step % 2, hi + step % 2)
            assert_exact(t, h)
            for _ in range(ticks):
                server.idle()
        for _ in range(8):  # the think time between drags
            server.idle()
    assert t.scheduler.units > 0
    assert t.cache_stats()["serve"]["think_time_messages"] > 0


def test_at_most_one_unit_is_outstanding_and_step_counts_its_wait():
    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    for _ in range(6):
        server.idle()
        unit = t.scheduler._unit
        mine = {id(f) for f in unit.outputs} if unit is not None else set()
        others = [f for f in t.store._data.values() if id(f) not in mine]
        assert all(leaf.is_ready() for f in others
                   for leaf in jax.tree_util.tree_leaves(f.field))
    # the next brush invalidates the pass; a fresh unit is on the device
    brush(server, h, "delay_bucket", 2, 5)
    assert server.idle() > 0 and t.scheduler.unit_pending
    before = server.stats_.think_wait_s
    brush(server, h, "delay_bucket", 3, 6)
    assert not t.scheduler.unit_pending
    assert server.stats_.think_wait_s > before
    assert t.cache_stats()["serve"]["think_wait_s"] == server.stats_.think_wait_s


def test_idle_polls_while_the_unit_runs_and_leaves_the_wait_to_the_step(monkeypatch):
    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    assert server.idle() > 0 and t.scheduler.unit_pending
    unit, units = t.scheduler._unit, t.scheduler.units
    # the unit is still on the device: idle dispatches nothing and is back
    # within a poll, without waiting for it
    monkeypatch.setattr(t.scheduler, "unit_ready", lambda: False)
    assert server.idle() == 0
    assert t.scheduler._unit is unit and t.scheduler.units == units
    assert server.stats_.think_wait_s == 0.0
    # the event that fell due meanwhile waits for the unit in step(), counted
    brush(server, h, "delay_bucket", 2, 5)
    assert not t.scheduler.unit_pending and server.stats_.think_wait_s > 0.0


def test_unit_after_a_dates_brush_puts_flights_to_dates_and_the_next_step_reads_it():
    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    brush(server, h, "dow", 1, 3)  # a Dates attribute: no viz is its source
    rerendered = list(h.last_result.results)
    assert sorted(rerendered) == sorted(VIZZES)
    wanted = edge_sigs(t, h, "bag:Flights", "bag:Dates")
    missing = {s for s in wanted if s not in t.store._data}
    assert missing
    c0 = store_counts(t)
    server.idle()  # one unit
    assert wanted <= set(t.store._data)
    assert missing <= t.store._think_unread
    c1 = store_counts(t)
    assert c1["think_puts"] - c0["think_puts"] >= len(missing)
    # the drag's next step roots at Dates and reads every Flights→Dates
    # message the unit wrote, and no other entry the drain wrote
    brush(server, h, "dow", 2, 4)
    c2 = store_counts(t)
    assert c2["think_read"] - c1["think_read"] == len(missing)
    assert not missing & t.store._think_unread
    assert_exact(t, h)


def test_a_brush_on_another_bag_drops_the_stale_think_entries():
    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    brush(server, h, "dow", 1, 3)
    server.idle()
    unread = set(t.store._think_unread)
    to_dates = edge_sigs(t, h, "bag:Flights", "bag:Dates") & unread
    assert to_dates
    c1 = store_counts(t)
    # a Carrier σ changes every message out of Flights toward Dates
    brush(server, h, "carrier_group", 0, 2)
    c2 = store_counts(t)
    dropped = c2["think_dropped"] - c1["think_dropped"]
    read = c2["think_read"] - c1["think_read"]
    assert not to_dates & t.store._think_unread
    assert dropped >= len(to_dates)
    # every entry the unit wrote is either read by the step or dropped,
    # unless a current query still reads it
    assert dropped + read + len(unread & t.store._think_unread) == len(unread)
    assert_exact(t, h)


def test_unit_and_think_wait_spans_carry_their_ids(tmp_path):
    from jax.profiler import ProfileData

    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    with jax.profiler.trace(str(tmp_path)):
        server.idle()
        assert t.scheduler.unit_pending
        brush(server, h, "delay_bucket", 2, 5)
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]

    def spans(name):
        return [(ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                for ev in events if ev.name == name]

    (idle,), (unit,) = spans("treant.serve.idle"), spans("treant.scheduler.unit")
    assert idle[0] <= unit[0] and unit[1] <= idle[1]
    assert unit[2]["messages"] > 0 and unit[2]["rows"] > 0
    (step,), (wait,) = spans("treant.serve.step"), spans("treant.serve.think_wait")
    assert step[0] <= wait[0] and wait[1] <= step[1]


def test_a_session_expected_back_holds_the_rest_of_its_pass():
    t, server, h = served()
    brush(server, h, "delay_bucket", 1, 4)
    brush(server, h, "dow", 1, 3)
    # the session came back 0.3 s after each of its last two results, and
    # at the rate of the last unit its passes cannot finish before then
    h._gaps.extend([0.3, 0.3])
    h._served_at = time.perf_counter()
    t.scheduler._unit_rate = 1.0
    away = edge_sigs(t, h, "bag:Flights", "bag:Carrier")
    assert not away & set(t.store._data)
    server.idle()  # the upward half: every message toward Dates
    assert edge_sigs(t, h, "bag:Flights", "bag:Dates") <= set(t.store._data)
    t.scheduler.wait_unit()
    calls = 0
    until = h._served_at + 2 * 0.3  # twice the pace
    while time.perf_counter() < until:  # nothing else may run
        t0 = time.perf_counter()
        assert server.idle() == 0 and not t.scheduler.unit_pending
        assert time.perf_counter() - t0 < 0.2  # a poll, not the 0.6 s hold
        calls += 1
    assert calls > 1
    assert not away & set(t.store._data)
    while t.scheduler.pending():  # the hold is over: the rest follows
        server.idle()
    assert away <= set(t.store._data)
    # passes that would finish before the session is back are not held
    brush(server, h, "dow", 2, 4)
    h._gaps.extend([2.0, 2.0])
    t.scheduler._unit_rate = float("inf")
    assert t.scheduler.finishes_within(h.id, 2.0)
    while t.scheduler.pending():
        server.idle()
    assert time.perf_counter() < h._served_at + 2.0
    assert edge_sigs(t, h, "bag:Flights", "bag:Carrier") <= set(t.store._data)


def test_a_budgeted_level_dispatches_a_chunk_at_a_time_to_the_same_messages():
    from repro.core.calibration import UnitBudget

    def calibrate(budgeted):
        t, server, h = served()
        brush(server, h, "dow", 1, 3)
        q = h.query_of("sum_by_state")
        eng = t.engine_for(q.ring_name, q.measure)
        plan, calls = eng.calibration_plan(q), 0
        while not plan.done:
            budget = UnitBudget(1.0) if budgeted else None
            eng.run_calibration_level([plan], budget=budget)
            if budgeted:  # the first dispatch always fits, the next does not
                assert budget.messages <= 1
            calls += 1
        placement = eng.place_predicates(q)
        return calls, {
            (u, v): jax.tree_util.tree_leaves(t.store.get(
                eng.edge_sig(q, u, v, placement), eng.gamma_carry(q, u, v)).field)
            for u, v in t.jt.directed_edges()
        }

    levels, whole = calibrate(False)
    calls, unit = calibrate(True)
    assert calls > levels
    assert unit.keys() == whole.keys()
    for edge, leaves in whole.items():
        for a, b in zip(unit[edge], leaves, strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
