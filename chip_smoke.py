#!/usr/bin/env python3
"""Smoke run of the served dashboard path on a TPU, at deployment size.

Drives ``TreantServer`` → ``Session`` → ``CJTEngine`` → ``PlanCache`` →
Pallas kernels over a 7,000,000-row flight table: about one year of the US
BTS Airline On-Time Performance table, the source of IDEBench's flights data
(400 airports, 30 carriers, 365 days).  Four analysts each open a
ten-viz crossfilter dashboard (SUM, COUNT and MAX vizzes), brush it, think,
and brush again; then every viz is checked against the plain
join-then-aggregate reference (``benchmarks/baselines.NaiveExecutor``).

    python chip_smoke.py              # one chip: the whole served path
    python chip_smoke.py --chips 4    # only the row-sharded path, on 4 chips

Everything runs in this one process.  Data comes from ``--seed``; the only
outputs are stdout and JAX's compilation cache (``repro.compile_cache``).
The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from benchmarks.baselines import NaiveExecutor  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    DashboardSpec, SetFilter, Treant, VizSpec, jt_from_catalog,
)
from repro.core import distributed as dist  # noqa: E402
from repro.core import semiring as sr  # noqa: E402
from repro.relational import schema  # noqa: E402
from repro.serve.server import TreantServer  # noqa: E402

N_FLIGHTS = 7_000_000
SESSIONS = 4
SEED = 1
# f32 engine sums against the float64 reference
RTOL, ATOL = 1e-4, 1e-2

_MEASURE = ("Flights", "dep_delay")


def dashboard_spec() -> DashboardSpec:
    """The 8-viz exploration dashboard plus one MAX and one COUNT viz, so
    SUM, COUNT and MAX all reach the kernels."""
    m = _MEASURE
    return DashboardSpec(vizzes=(
        VizSpec("by_state", measure=m, ring="sum", group_by=("airport_state",)),
        VizSpec("by_size", measure=m, ring="sum", group_by=("airport_size",)),
        VizSpec("by_carrier", measure=m, ring="sum", group_by=("carrier_group",)),
        VizSpec("by_delay", measure=m, ring="sum", group_by=("delay_bucket",)),
        VizSpec("by_month", measure=m, ring="sum", group_by=("month",)),
        VizSpec("by_dow", measure=m, ring="sum", group_by=("dow",)),
        VizSpec("state_by_size", measure=m, ring="sum",
                group_by=("airport_state", "airport_size")),
        VizSpec("carrier_by_month", measure=m, ring="sum",
                group_by=("carrier_group", "month")),
        VizSpec("max_delay_by_month", measure=m, ring="tropical_max",
                group_by=("month",)),
        VizSpec("flights_by_carrier", ring="count", group_by=("carrier_group",)),
    ))


def session_events(i: int) -> list[SetFilter]:
    """Analyst ``i``'s brushes: a date range, a state IN-list and a
    delay-bucket range (each analyst looks at a different slice)."""
    d0 = (37 * i) % 300
    return [
        SetFilter("date_id", lo=d0, hi=d0 + 60),
        SetFilter("airport_state", values=tuple((5 * i + k) % 52 for k in range(4)),
                  source="by_state"),
        SetFilter("delay_bucket", lo=i % 4, hi=i % 4 + 5, source="by_delay"),
    ]


def final_brush(i: int) -> SetFilter:
    return SetFilter("carrier_group", values=(i % 6, (i + 2) % 6), source="by_carrier")


class _Compiles:
    """Counts XLA compile requests and persistent-cache hits (jax.monitoring)."""

    _installed = None

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0

    @classmethod
    def install(cls) -> "_Compiles":
        if cls._installed is None:
            c = cls._installed = cls()

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    c.cache_hits += 1

            def on_duration(event, secs, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    c.requests += 1
                    c.seconds += secs

            monitoring.register_event_listener(on_event)
            monitoring.register_event_duration_secs_listener(on_duration)
        return cls._installed

    def snapshot(self) -> tuple[int, int, float]:
        return self.requests, self.cache_hits, self.seconds


def _bytes_in_use() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use")


def _compare(got: np.ndarray, ref: np.ndarray) -> tuple[bool, float, float]:
    """(within tolerance, max abs error, max error relative to the scale)."""
    fin = np.isfinite(ref)
    same_empty = np.array_equal(np.isfinite(got), fin) and np.array_equal(
        got[~fin], ref[~fin])
    err = float(np.max(np.abs(got[fin] - ref[fin]), initial=0.0))
    scale = float(np.max(np.abs(ref[fin]), initial=1.0))
    ok = same_empty and bool(np.allclose(got[fin], ref[fin], rtol=RTOL, atol=ATOL))
    return ok, err, err / max(scale, 1.0)


def run(n_flights: int = N_FLIGHTS, sessions: int = SESSIONS, seed: int = SEED,
        mesh=None, log=print) -> dict:
    """Every phase of the smoke; raises ``AssertionError`` if any fails.

    ``mesh`` row-shards the fact table over a device mesh (``None`` runs on
    one device).  Returns the per-(session, viz) results, their errors
    against the reference and the engine's ``cache_stats()``.
    """
    compiles = _Compiles.install()
    phases: list[tuple[str, float, tuple]] = []

    def phase(name, t0, c0):
        c1 = compiles.snapshot()
        dt = time.perf_counter() - t0
        phases.append((name, dt, c1))
        log(f"phase {name}: {dt:.3f} s, {c1[0] - c0[0]} compiles "
            f"({c1[1] - c0[1]} from the persistent cache, {c1[2] - c0[2]:.3f} s)")
        return time.perf_counter(), c1

    t0, c0 = time.perf_counter(), compiles.snapshot()
    # -- data: the fact table and its dimensions, codes placed on the device
    cat = schema.flight(n_flights=n_flights, seed=seed)
    treant = Treant(cat, ring=sr.SUM, jt=jt_from_catalog(cat),
                    mesh=mesh if mesh is not None else False)
    flights = cat.get("Flights")
    jax.block_until_ready([cat.dev_flat_codes(flights, (a,))[0] for a in flights.attrs])
    log(f"rows {flights.num_rows} bucket {flights.row_bucket}")
    log(f"device bytes_in_use after load: {_bytes_in_use()}")
    t0, c0 = phase("load", t0, c0)

    # -- sessions: open and calibrate every analyst's dashboard
    server = TreantServer(treant)
    handles = [server.open_session(dashboard_spec(), name=f"analyst{i}")
               for i in range(sessions)]
    treant.store.block_until_ready()
    t0, c0 = phase("open_calibrate", t0, c0)

    def drain():
        while server.step():
            pass
        treant.store.block_until_ready()
        jax.block_until_ready([
            r.factor.field for h in handles if h.last_result is not None
            for r in h.last_result.results.values()
        ])

    # -- events: three brushes per analyst through the server's queue
    for i, h in enumerate(handles):
        for ev in session_events(i):
            h.submit(ev)
    drain()
    t0, c0 = phase("events", t0, c0)

    # -- think time, then one more brush each
    server.idle()
    treant.store.block_until_ready()
    t0, c0 = phase("idle", t0, c0)
    for i, h in enumerate(handles):
        h.submit(final_brush(i))
    drain()
    t0, c0 = phase("brush", t0, c0)

    # -- check: every viz of every session against the reference
    naive = NaiveExecutor(cat, "Flights")
    spec = dashboard_spec()
    results: dict[tuple[str, str], np.ndarray] = {}
    worst: dict[str, list] = {}
    failed = []
    for h in handles:
        for v in spec.vizzes:
            q = h.query_of(v.name)
            got = np.asarray(h.read(v.name).factor.project_to(q.group_by).field)
            ref = naive.execute(q, op="max" if v.ring == "tropical_max" else "sum")
            ok, err, rel = _compare(got.astype(np.float64), ref)
            results[(h.id, v.name)] = got
            w = worst.setdefault(v.name, [0.0, 0.0])
            w[0], w[1] = max(w[0], err), max(w[1], rel)
            if not ok:
                failed.append((h.id, v.name, err))
    log(f"tolerance: allclose(rtol={RTOL}, atol={ATOL}) against NaiveExecutor")
    for name, (err, rel) in worst.items():
        log(f"viz {name}: max abs err {err!r}, max rel err {rel!r}")
    t0, c0 = phase("check", t0, c0)

    stats = treant.cache_stats()
    plans, serve = stats["plans"], stats["serve"]
    log(f"device bytes_in_use at end: {_bytes_in_use()}")
    log("cache_stats: " + json.dumps({
        "kernel_execs": plans["kernel_execs"],
        "fallback_execs": plans["fallback_execs"],
        "fused_level_launches": plans["fused_level_launches"],
        "fused_level_messages": plans["fused_level_messages"],
        "plans_built": plans["plans_built"],
        "shard_execs": plans["shard_execs"],
        "allreduce_bytes": plans["allreduce_bytes"],
        "serve_events": serve["events_processed"],
        "serve_batches": serve["batches"],
        "serve_errors": serve["errors"],
        "messages": stats["messages"],
        "store_bytes": stats["bytes"],
    }))
    assert not failed, f"vizzes outside tolerance: {failed}"
    assert serve["errors"] == 0, f"serve errors: {serve['errors']}"
    assert serve["events_processed"] == 4 * sessions, serve
    assert plans["kernel_execs"] > 0, "no plan ran a Pallas kernel"
    assert plans["fused_level_launches"] > 0, "no fused level launch"
    return {"results": results, "worst": worst, "stats": stats, "phases": phases}


def run_sharded(chips: int, n_flights: int = N_FLIGHTS, sessions: int = SESSIONS,
                seed: int = SEED, log=print) -> dict:
    """The row-sharded path over ``chips`` devices, then the same run on one
    device; COUNT vizzes must agree bit for bit."""
    mesh = dist.make_engine_mesh(chips)
    log(f"== sharded over {chips} devices")
    sharded = run(n_flights, sessions, seed, mesh=mesh, log=log)
    assert sharded["stats"]["plans"]["shard_execs"] > 0, "no sharded dispatch"
    log("== unsharded on one device")
    single = run(n_flights, sessions, seed, log=log)
    counts = [v.name for v in dashboard_spec().vizzes if v.ring == "count"]
    for key, got in sharded["results"].items():
        if key[1] in counts:
            assert np.array_equal(got, single["results"][key]), f"COUNT differs: {key}"
    log(f"COUNT vizzes bit-identical sharded vs one device: {counts}")
    return sharded


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded path over four chips")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"needs {args.chips} chips, JAX reports {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 1:
        run(seed=args.seed)
    else:
        run_sharded(args.chips, seed=args.seed)
    print(f"total wall time: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
