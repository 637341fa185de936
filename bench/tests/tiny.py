"""A cell of the benchmark at a size the CPU can run, kernels interpreted."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import json  # noqa: E402

from bench import run  # noqa: E402

SCALE = 0.001  # 7,000 flights, 7,700 trips
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# a configuration and mix under bench/ that are not a cell of BENCHMARK.json
CANDIDATES = ["flight-7m.crossfilter-1"]
WORKLOADS = [w["name"] for w in SPEC["workloads"]] + \
    [c for c in CANDIDATES if c not in {w["name"] for w in SPEC["workloads"]}]


def load(workload: str) -> dict:
    """The workload's cell; a candidate ``<config>.<traffic>`` is loaded as
    if ``BENCHMARK.json`` named it, with every per-layer metric."""
    spec = json.loads(json.dumps(SPEC))
    if workload not in {w["name"] for w in spec["workloads"]}:
        config, traffic = workload.split(".", 1)
        spec["configs"].append({"name": config, "file": f"bench/configs/{config}.json"})
        spec["workloads"].append({"name": workload, "config": config, "traffic": traffic,
                                  "chips": 1})
        for m in spec["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(workload)
    return run.load_cell(workload, spec)


def cell(workload: str) -> dict:
    """The workload's cell with at most two analysts, short waits and a short warm-up."""
    c = load(workload)
    mix = dict(c["mix"], analysts=min(2, int(c["mix"]["analysts"])), warmup_events=3)
    mix["think_mean_s"] = min(mix["think_mean_s"], 0.3)
    mix["step_gap_s"] = min(mix["step_gap_s"], 0.05)
    c["mix"] = mix
    return c


def run_tiny(monkeypatch, workload: str, seed: int = 5, control: bool = False,
             seconds: float = 1.5) -> dict:
    # route every eligible reduction to the kernels, as a TPU process does
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", str(1 << 40))
    return run.run_cell(cell(workload), seed, seconds, False, None, scale=SCALE, control=control)
