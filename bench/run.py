#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its configuration,
traffic mix, per-layer metric readers and correctness limit are found by name:
``bench/configs/<config>.json`` (the ``file`` of its ``configs`` entry),
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/limits/<workload>.json``.  Nothing here branches on a name.

One process runs the cell: tables from ``--seed`` on the host, codes on the
device, the sessions opened, a fixed warm-up of the cell's own traffic, then
``--seconds`` of served traffic, then the check against the plain reference.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, a few seconds of the window traced by
the profiler.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.  The last line of stdout is the result;
the numbers compared, each beside its limit, end stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

SAMPLE_EVENTS = 24     # events whose whole screen is compared after the window
TRACE_SECONDS = 12.0   # longest traced slice of a --trace 1 run


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str, spec: dict | None = None) -> dict:
    """The workload entry with its configuration, mix, limits and readers,
    from ``BENCHMARK.json`` or from ``spec`` in its place."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    readers = {}
    for m in spec["per_layer"]:
        if wl["name"] in m.get("workloads", [wl["name"]]):
            path = BENCH / "metrics" / f"{m['name']}.py"
            mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{m['name']}", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            readers[m["name"]] = (m, mod)
    return {
        "workload": wl,
        "config": json.loads((ROOT / entry["file"]).read_text()),
        "mix": json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text()),
        "limits": json.loads((BENCH / "limits" / f"{wl['name']}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"]
                       if wl["name"] in m.get("workloads", [wl["name"]])],
        "readers": readers,
    }


def _flat(d: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[prefix + k] = v
    return out


def counters(system) -> dict:
    c = _flat(system.treant.cache_stats())
    c["session_prefetch_hits"] = sum(h.session.prefetch_hits for h in system.handles)
    return c


def served_array(factor, group_by) -> np.ndarray | None:
    """A served result as a host array in the viz's group-by order."""
    if factor is None or set(factor.attrs) != set(group_by) or len(factor.attrs) != len(group_by):
        return None
    field = np.asarray(factor.field)
    return np.transpose(field, [factor.attrs.index(a) for a in group_by])


def sample(records, seed: int) -> list:
    """Events whose screens are compared: drawn from the seed, plus one
    event for each way of serving a viz that the draw missed."""
    ok = [r for r in records if not r.failed]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    pick = sorted(rng.choice(len(ok), min(SAMPLE_EVENTS, len(ok)), replace=False).tolist()) \
        if ok else []
    have = {route for i in pick for route in ok[i].routes.values()}
    for route in sorted({route for r in ok for route in r.routes.values()} - have):
        cands = [i for i, r in enumerate(ok) if route in r.routes.values()]
        pick.append(int(rng.choice(cands)))
    return [ok[i] for i in pick]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, peaks: dict | None,
             scale: float = 1.0, control: bool = False) -> dict:
    """Set-up, window, check; returns the result line's fields.

    ``scale`` shrinks the fact tables (tests on the CPU).  ``control`` also
    reads the control, the reference in bfloat16 put in the program's place,
    on the same events (``control_max_rel_err``); the benchmark's own runs
    do not.
    """
    from bench import check, generator, harness, trace_reduce
    from bench.reference import Reference

    cfg, mix = cell["config"], cell["mix"]
    compiles = harness.Compiles.install()
    t = time.perf_counter()
    system = harness.build(cfg, seed, int(mix["analysts"]), scale)
    log(f"set-up: tables, codes and {mix['analysts']} sessions open "
        f"{time.perf_counter() - t!r} s, {compiles.snapshot()[0]} compiles so far")
    loop = harness.ServingLoop(system, mix)
    t = time.perf_counter()
    loop.warm(generator.sweeps(mix, cfg),
              generator.analysts(mix, cfg, seed, generator.WARMUP_STREAM),
              int(mix["warmup_events"]))
    requests, hits, secs = compiles.snapshot()
    log(f"set-up: warm-up {time.perf_counter() - t!r} s, {len(loop.step_s)} steps, "
        f"{len(loop.idle_s)} idle calls; {requests} compiles so far "
        f"({hits} persistent-cache hits, {secs!r} s)")
    loop.step_s.clear()
    loop.idle_s.clear()
    loop.attach(generator.analysts(mix, cfg, seed, generator.WINDOW_STREAM))
    tracing = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        slice_s = min(TRACE_SECONDS, seconds / 3)
        tracing = (seconds / 4, seconds / 4 + slice_s, str(TRACE_DIR))
    before, c0 = counters(system), compiles.snapshot()
    gc.collect()
    gc.freeze()
    records, t0, t_end = loop.window(seconds, tracing)
    after, c1 = counters(system), compiles.snapshot()
    gc.unfreeze()
    setup_s = t0 - T_START

    import jax
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    # -- end to end -------------------------------------------------------------
    lat = np.array([np.inf if r.failed else r.done - r.due for r in records])
    done = [r for r in records if not r.failed]
    last = max((r.done for r in done), default=t_end)
    method = "linear" if np.isfinite(lat).all() else "higher"
    e2e = {
        "setup_s": setup_s,
        "event_p50_ms": 1e3 * float(np.percentile(lat, 50, method=method)) if len(lat) else None,
        "events_per_s": len(done) / (last - t0) if done else None,
    }
    late = np.array([r.submitted - r.due for r in records]) * 1e3
    if len(late):
        log(f"generator lateness ms: mean {float(late.mean())!r} "
            f"p95 {float(np.percentile(late, 95))!r} max {float(late.max())!r} "
            f"over {len(late)} events")
    log(f"window {t_end - t0!r} s, last event done {last - t0!r} s after the start")
    if len(lat):
        log(f"event latency ms: p90 {1e3 * float(np.percentile(lat, 90, method=method))!r} "
            f"max {1e3 * float(lat.max())!r} over {len(lat)} events")
    for name, xs in (("step", loop.step_s), ("idle", loop.idle_s)):
        if xs:
            log(f"{name} calls {len(xs)}: mean {float(np.mean(xs))!r} s, max {max(xs)!r} s, "
                f"total {sum(xs)!r} s")
    for err in loop.errors[:3]:
        log(f"step raised: {err}")

    # -- per layer --------------------------------------------------------------
    delta = {k: after[k] - before.get(k, 0) for k in after}
    compiled = c1[0] - c0[0]
    names = {}
    for n in compiles.names[c0[0]:c1[0]]:
        names[n] = names.get(n, 0) + 1
    log(f"compiles in window: {compiled} ({c1[1] - c0[1]} persistent-cache hits, "
        f"{c1[2] - c0[2]!r} s): {json.dumps(names)}")
    log("window counters: " + json.dumps({k: v for k, v in delta.items() if v}))
    reduced = None
    traced_events = 0
    if trace and loop.traced is not None:
        ta, tb = loop.traced
        traced_events = sum(1 for r in done if ta <= r.done <= tb)
        kernels = tuple(k for _, mod in cell["readers"].values() for k in getattr(mod, "KERNELS", ()))
        reduced = trace_reduce.reduce(trace_reduce.find(str(TRACE_DIR)), tuple(dict.fromkeys(kernels)))
        log(f"traced {tb - ta!r} s host, {reduced['window_s']!r} s profile, "
            f"{traced_events} events, {len(reduced['calls'])} kernel calls")
    w = {
        "counters": delta, "events": len(done), "compiles": compiled,
        "rerendered": sum(len(r.routes) for r in done),
        "trace": reduced, "traced_events": traced_events, "peaks": peaks,
    }
    layer = {name: mod.read(w) for name, (_, mod) in cell["readers"].items()}

    # -- correctness: served results against the reference, program freed -------
    chosen = sample(records, seed)
    groups = {v["name"]: v["group_by"] for v in cfg["dashboard"]}
    # every viz of the dashboard: one the analyst never received reads as missing
    answers = [(r.filters, {viz: served_array(r.screen.get(viz), g) for viz, g in groups.items()})
               for r in chosen]
    routes: dict[str, int] = {}
    for r in chosen:
        for route in r.routes.values():
            routes[route] = routes.get(route, 0) + 1
    data = system.data
    del system, loop, records, done, chosen
    gc.collect()
    t_ref = time.perf_counter()
    reference = Reference(cfg, data)
    result = check.compare(answers, reference)
    if control:
        low = Reference(cfg, data, "bfloat16")
        controlled = [(f, {viz: low.answer(viz, f).astype(np.float32) for viz in s})
                      for f, s in answers]
        del low
        result["control_max_rel_err"] = check.compare(controlled, reference)["max_rel_err"]
    log(f"check: {result['results']} results of {len(answers)} events, routes re-rendered "
        f"{json.dumps(routes)}, worst by ring "
        + json.dumps({k: v for k, v in result.items() if k.startswith("worst_")})
        + f", reference {time.perf_counter() - t_ref!r} s")
    limit = float(cell["limits"]["max_rel_err"]["limit"])
    correct = bool(result["results"] > 0 and result["max_rel_err"] <= limit)
    return {
        "correct": correct,
        "attempted": len(lat),
        "failed": int(np.sum(~np.isfinite(lat))),
        "end_to_end": e2e,
        "per_layer": layer,
        "memory_peak_bytes": mem,
        "trace": reduced,
        "check": {"max_rel_err": {"value": result["max_rel_err"], "limit": limit}},
        "readings": result,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    from bench.trace_reduce import breakdown

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX reports {devices[0].platform}")
        return 2
    if len(devices) < int(cell["workload"]["chips"]):
        log(f"{args.workload} needs {cell['workload']['chips']} chips, JAX reports {len(devices)}")
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        log(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks[kind])
    if args.trace:
        metrics = {n: {"value": v, "unit": m["unit"]}
                   for n, (m, _) in cell["readers"].items()
                   if (v := out["per_layer"][n]) is not None}
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in out["end_to_end"].items() if n in units and v is not None}
        for name, value in out["per_layer"].items():
            log(f"per-layer {name}: {value!r}")
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out["trace"] is not None:
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        line["breakdown"] = breakdown(out["trace"])
    line["check"] = out["check"]
    for name, c in out["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
