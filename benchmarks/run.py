"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes a machine-readable
``BENCH_<suite>.json`` summary (name → us_per_call) per executed suite, so
CI can upload perf artifacts and the trajectory accumulates.  Scales are
reduced for the 1-vCPU container; relative speedups (the paper's claims) are
scale-stable.

  python -m benchmarks.run              # all
  python -m benchmarks.run compiled     # one suite

``REPRO_BENCH_OUT`` overrides the JSON output directory (default: cwd).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

from . import common


SUITES = [
    ("chain", "bench_chain", "Fig 23: JT vs No-JT on chain joins"),
    ("dashboard", "bench_dashboard", "Fig 13: Salesforce dashboard"),
    ("compiled", "bench_compiled", "Compiled message plans: jit+Pallas vs legacy"),
    ("flight", "bench_flight", "Fig 14/16: Flight/IDEBench workload"),
    ("think_time", "bench_think_time", "Fig 15: calibration sensitivity"),
    ("updates", "bench_updates", "Delta calibration: update-then-query vs rebuild"),
    ("ingest", "bench_ingest", "Streaming ingestion: coalesced ticks vs no-ingest baseline"),
    ("serve", "bench_serve", "Multi-tenant serving: cross-session batched fan-out + byte budget"),
    ("explore", "bench_explore", "Exploratory BI: predictive think-time + bin cubes vs σ-prefetch"),
    ("sharded", "bench_sharded", "Sharded CJT over a device mesh: rows/sec scaling 1-8 devices"),
    ("ml_aug", "bench_ml_augmentation", "Fig 18: factorized-ML augmentation"),
    ("tpch", "bench_tpch", "Fig 19/20: TPC-H dashboard"),
    ("empty_bag", "bench_empty_bag", "Fig 21: empty-bag optimization"),
    ("cube", "bench_cube", "Fig 24/25: data cubes over CJTs"),
]


def _write_json(key: str, rows) -> None:
    out_dir = os.environ.get("REPRO_BENCH_OUT", ".")
    path = os.path.join(out_dir, f"BENCH_{key}.json")
    with open(path, "w") as fh:
        json.dump({name: round(us, 3) for name, us, _ in rows}, fh,
                  indent=2, sort_keys=True)
        fh.write("\n")
    print(f"# wrote {path}", flush=True)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(sys.argv[1:])
    failures = []
    print("name,us_per_call,derived")
    for key, module, desc in SUITES:
        if want and key not in want:
            continue
        print(f"# === {key}: {desc} ===", flush=True)
        t0 = time.time()
        start = len(common.ROWS)
        try:
            mod = __import__(f"benchmarks.{module}", fromlist=["main"])
            mod.main()
        except Exception:
            failures.append(key)
            traceback.print_exc()
        # failed suites get no JSON: a truncated summary in the perf
        # trajectory is worse than a missing one
        if len(common.ROWS) > start and key not in failures:
            _write_json(key, common.ROWS[start:])
        print(f"# === {key} done in {time.time() - t0:.1f}s ===", flush=True)
    # kernel roofline microbench: measures launch overhead / crossover for the
    # Pallas kernels and writes kernel_costs.json (nightly refresh; the
    # committed benchmarks/baselines/kernel_costs.json seeds the cost model)
    if not want or "roofline" in want:
        try:
            from . import roofline
            print("# === roofline: kernel microbench ===")
            roofline.main()
        except Exception:
            failures.append("roofline")
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
