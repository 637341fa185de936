"""The taxi cell through the harness at a tiny size: served results match
the reference, and the control (the reference in bfloat16) does not."""

from bench.tests import tiny


def test_taxi_jump_matches_reference_and_control_fails(monkeypatch):
    out = tiny.run_tiny(monkeypatch, "taxi-7m.jump-16", control=True)
    r = out["readings"]
    assert out["correct"], r
    assert out["failed"] == 0 and out["attempted"] > 0
    assert r["results"] > 0
    assert r["control_max_rel_err"] > out["check"]["max_rel_err"]["limit"], r
    # no think time: the queue never empties, every step batches both analysts
    assert out["per_layer"]["serve_batch_width"] == 2
