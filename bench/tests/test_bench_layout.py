"""The benchmark's files: found by name, generators repeatable, no result
without a TPU, and ``BENCHMARK.json`` within the shape its readers expect."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import generator, run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_loader_finds_every_piece_of_a_cell_by_name(workload):
    cell = run.load_cell(workload)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["mix"]["name"] == cell["workload"]["traffic"]
    assert set(cell["readers"]) == {m["name"] for m in SPEC["per_layer"]
                                    if workload in m.get("workloads", [workload])}
    for _, mod in cell["readers"].values():
        assert callable(mod.read)
    assert cell["limits"]["max_rel_err"]["limit"] > 0


@pytest.mark.parametrize("workload", tiny.CANDIDATES)
def test_candidate_loads_like_a_cell_with_every_metric(workload):
    cell = tiny.load(workload)
    assert cell["workload"]["name"] == workload
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["mix"]["name"] == cell["workload"]["traffic"]
    assert set(cell["readers"]) == {m["name"] for m in SPEC["per_layer"]}
    assert cell["limits"]["max_rel_err"]["limit"] > 0


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no-such-cell")


def test_names_units_and_keys_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    ends = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in ends
    for m in SPEC["per_layer"]:
        assert m["moves"] in ends
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_generators_repeat_for_a_seed_and_seeds_share_the_work(workload):
    cell = tiny.load(workload)
    mix, cfg = cell["mix"], cell["config"]

    def draw(seed, stream=generator.WINDOW_STREAM, n=60):
        return [[(s.event, s.delay_s) for s in (a.next() for _ in range(n))]
                for a in generator.analysts(mix, cfg, seed, stream)]

    big = 2**33 + 17  # seeds wider than 32 bits
    assert draw(big) == draw(big)
    assert draw(big) != draw(big + 1)
    assert draw(big) != draw(big, generator.WARMUP_STREAM)
    # another seed draws the same cycle of think times, in another order
    k = int(mix["think_strata"])
    n = k * (mix["episode_steps"][1] + 1)

    def thinks(seed):
        return [sorted([d for _, d in a if d > mix["step_gap_s"]][:k]) for a in draw(seed, n=n)]

    if mix["think_mean_s"] > 0:
        assert thinks(big) == thinks(3)
        assert all(len(t) == k for t in thinks(big))
    # the seed draws the script too: which dimension each episode takes
    # differs between seeds, the dimensions of a round do not

    def episodes(seed):
        """The dimension of each episode, in order, per analyst."""
        return [[a[0][0]["attr"]] + [e["attr"] for (e, d), (p, _) in zip(a[1:], a)
                                     if e["op"] == "set" and (d > mix["step_gap_s"]
                                                              or p["op"] == "clear")]
                for a in draw(seed, n=100)]

    assert episodes(big) != episodes(3)
    dims = sorted(f["attr"] for f in generator.mix_dims(mix, cfg))
    for taken in episodes(big) + episodes(3):
        assert len(taken) >= len(dims)
        assert sorted(taken[:len(dims)]) == dims  # the first round takes each once
    for analyst in draw(big):
        for ev, delay in analyst:
            assert delay >= 0
            if ev["op"] == "set":
                d = cfg["domains"][ev["attr"]]
                if ev["values"]:
                    assert 0 < len(ev["values"]) < d and max(ev["values"]) < d
                else:
                    assert 0 <= ev["lo"] < ev["hi"] <= d


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_sweep_is_the_same_for_every_seed_and_takes_every_dimension(workload):
    cell = tiny.load(workload)
    mix, cfg = cell["mix"], cell["config"]

    def sweep():
        return [[(s.event, s.delay_s) for s in (a.next() for _ in range(a.length))]
                for a in generator.sweeps(mix, cfg)]

    first = sweep()
    assert first == sweep()
    assert all(a == first[0] for a in first)  # in step, the same values
    events = [e for e, _ in first[0]]
    assert all(d == 0.0 for _, d in first[0])
    dims = [f["attr"] for f in generator.mix_dims(mix, cfg)]
    sets = [e["attr"] for e in events if e["op"] == "set"]
    assert sets == [a for a in dims for _ in range(2)] * int(mix["warmup_sweeps"])
    clears = [e["attr"] for e in events if e["op"] == "clear"]
    assert clears == (dims * int(mix["warmup_sweeps"]) if mix["backtrack"] else [])


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert '"correct"' not in line


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    _no_result(_run_py(ROOT))


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run_py(tmp_path, {"PYTHONPATH": ""}))
