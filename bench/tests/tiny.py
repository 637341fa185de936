"""A cell of the benchmark at a size the CPU can run, kernels interpreted.

A candidate is a ``<config>.<traffic>`` pair that ``BENCHMARK.json`` does
not name and whose files are under ``bench/``: its configuration, its mix
and its limits.  It is loaded as a cell named by the two entries that a
change adding it writes (``named``).  On the CPU every cell and candidate
gets every per-layer reader, whichever cells a metric's ``workloads`` list
names, so a reader that breaks on another configuration fails here.
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import json  # noqa: E402

from bench import run  # noqa: E402

BENCH = ROOT / "bench"
SCALE = 0.001  # 7,000 flights, 7,700 trips
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def split(workload: str) -> tuple[str, str] | None:
    """The ``(config, traffic)`` of a ``<config>.<traffic>`` name whose
    configuration and mix files are under ``bench/``, else None."""
    parts = workload.split(".")
    for i in range(1, len(parts)):
        config, traffic = ".".join(parts[:i]), ".".join(parts[i:])
        if (BENCH / "configs" / f"{config}.json").is_file() and \
                (BENCH / "traffic" / f"{traffic}.json").is_file():
            return config, traffic
    return None


CANDIDATES = [p.stem for p in sorted((BENCH / "limits").glob("*.json"))
              if p.stem not in {w["name"] for w in SPEC["workloads"]} and split(p.stem)]
WORKLOADS = [w["name"] for w in SPEC["workloads"]] + CANDIDATES


def named(workload: str, spec: dict = SPEC) -> dict:
    """A copy of ``spec`` that names the workload: for a candidate, a
    ``configs`` entry (unless its configuration is named already) and a
    ``workloads`` entry on one chip, as a change that adds the cell writes
    them; no metric's ``workloads`` list is touched."""
    spec = copy.deepcopy(spec)
    if workload in {w["name"] for w in spec["workloads"]}:
        return spec
    config, traffic = split(workload)
    if config not in {c["name"] for c in spec["configs"]}:
        cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
        spec["configs"].append({"name": config, "source": cfg["source"],
                                "file": f"bench/configs/{config}.json",
                                "reduced": cfg["reduced"], "why": f"candidate {config}"})
    spec["workloads"].append({"name": workload, "config": config, "traffic": traffic,
                              "chips": 1, "why": f"candidate {workload}"})
    return spec


def load(workload: str, spec: dict = SPEC) -> dict:
    """The workload's cell, named in ``spec`` if it is a candidate, with
    every per-layer reader: no metric's ``workloads`` list is honoured."""
    spec = named(workload, spec)
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    return run.load_cell(workload, spec)


def cell(workload: str, spec: dict = SPEC) -> dict:
    """The workload's cell with at most two analysts, short waits and a short warm-up."""
    c = load(workload, spec)
    mix = dict(c["mix"], analysts=min(2, int(c["mix"]["analysts"])), warmup_events=3)
    mix["think_mean_s"] = min(mix["think_mean_s"], 0.3)
    mix["step_gap_s"] = min(mix["step_gap_s"], 0.05)
    c["mix"] = mix
    return c


def run_tiny(monkeypatch, workload: str, seed: int = 5, control: bool = False,
             seconds: float = 1.5, spec: dict = SPEC) -> dict:
    # route every eligible reduction to the kernels, as a TPU process does
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", str(1 << 40))
    return run.run_cell(cell(workload, spec), seed, seconds, False, None, scale=SCALE,
                        control=control)
