"""The think-time readers at the tiny size: the flight cell's drain writes
entries its next steps read, and the taxi cell, whose analysts never think,
reads nothing."""

import pytest

from bench import generator, run
from bench.tests import tiny

FLIGHT = "flight-7m.crossfilter-1"


def _switching_seed(cell) -> int:
    """A seed whose first two drags brush attributes on different bags
    (``Dates`` and ``Flights``): the second drag's first step then reads
    what the think time after the first drag calibrated."""
    dates = {"date_id", "month", "dow"}
    gap = cell["mix"]["step_gap_s"]
    for seed in range(1, 100):
        analyst = generator.analysts(cell["mix"], cell["config"], seed,
                                     generator.WINDOW_STREAM)[0]
        steps = [analyst.next() for _ in range(9)]
        firsts = [s.event["attr"] for s in steps if s.delay_s > gap][:2]
        if len(firsts) == 2 and (firsts[0] in dates) != (firsts[1] in dates):
            return seed
    raise AssertionError("no seed switches bags")


def test_flight_crossfilter_reads_its_think_time(monkeypatch):
    cell = tiny.cell(FLIGHT)
    # the mix's own think time, so the drain between drags can finish
    cell["mix"]["think_mean_s"] = tiny.load(FLIGHT)["mix"]["think_mean_s"]
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", str(1 << 40))
    out = run.run_cell(cell, _switching_seed(cell), 7.0, False, None, scale=tiny.SCALE)
    assert out["correct"], out["readings"]
    r = out["per_layer"]
    assert r["think_read_pct"] > 0
    assert isinstance(r["think_wait_ms_per_event"], float)
    assert r["think_wait_ms_per_event"] >= 0


def test_taxi_jump_reads_no_think_time(monkeypatch):
    out = tiny.run_tiny(monkeypatch, "taxi-7m.jump-16", seed=7, seconds=1.0)
    assert out["correct"], out["readings"]
    assert out["per_layer"]["think_read_pct"] is None
    assert out["per_layer"]["think_wait_ms_per_event"] is None


@pytest.mark.parametrize("counters", [
    {},                                                      # a program without them
    {"store.think_puts": 0, "store.think_read": 0, "scheduler.units": 0,
     "serve.think_wait_s": 0.0, "serve.events_processed": 12},  # no think time ran
])
def test_think_readers_read_nothing_without_think_time(counters):
    w = {"counters": counters, "events": 12, "trace": None}
    readers = tiny.load(FLIGHT)["readers"]
    for name in ("think_read_pct", "think_wait_ms_per_event"):
        assert readers[name][1].read(w) is None
