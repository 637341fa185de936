"""chip_smoke.py on the CPU at a tiny size.

The chip smoke drives the served dashboard path (TreantServer → Session →
CJTEngine → PlanCache → Pallas kernels) and checks every viz against the
join-then-aggregate reference.  Here its ``run`` goes through the same
phases over 20k flights with the kernels interpreted, and ``main`` must
refuse to report a result without a TPU.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_run_matches_reference_at_tiny_size(monkeypatch):
    # route every eligible reduction to the kernels, as a TPU process does
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", str(1 << 40))
    lines = []
    out = chip_smoke.run(n_flights=20_000, sessions=2, log=lines.append)
    vizzes = {v.name for v in chip_smoke.dashboard_spec().vizzes}
    assert set(out["worst"]) == vizzes
    assert len(out["results"]) == 2 * len(vizzes)
    stats = out["stats"]
    assert stats["serve"]["errors"] == 0
    assert stats["plans"]["kernel_execs"] > 0
    assert stats["plans"]["fused_level_launches"] > 0
    # COUNT vizzes are exact, whatever the ⊕ order
    for (_, viz), got in out["results"].items():
        if viz == "flights_by_carrier":
            assert float(got.sum()).is_integer()
    assert any(line.startswith("rows 20000 bucket 32768") for line in lines)
    assert [p[0] for p in out["phases"]] == [
        "load", "open_calibrate", "events", "idle", "brush", "check"]


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        assert not (line.startswith("{") and json.loads(line).get("ok"))
