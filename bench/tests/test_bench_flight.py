"""The flight cell through the harness at a tiny size: served results match
the reference, and the control (the reference in bfloat16) does not."""

from bench.tests import tiny


def test_flight_crossfilter_matches_reference_and_control_fails(monkeypatch):
    out = tiny.run_tiny(monkeypatch, "flight-7m.crossfilter-1", control=True)
    r = out["readings"]
    assert out["correct"], r
    assert out["failed"] == 0 and out["attempted"] > 0
    assert r["results"] > 0
    assert r["control_max_rel_err"] > out["check"]["max_rel_err"]["limit"], r
    assert out["per_layer"]["serve_batch_width"] >= 1
    # every per-layer reader runs, whichever cells its metric lists
    assert set(out["per_layer"]) == {m["name"] for m in tiny.SPEC["per_layer"]}
    assert set(out["end_to_end"]) == {"setup_s", "event_p50_ms", "events_per_s"}
