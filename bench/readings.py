#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program and its control.

    python3 bench/readings.py --workload <name> --seeds 11,12,13 --seconds 6

For each seed, in one process, runs the cell as ``bench/run.py`` does, with
one warm-up event per analyst and a short window at the cell's own load,
and reads ``max_rel_err`` of the program's served results
and of the control (the reference computed in bfloat16, put in the
program's place) on the same events.  The lower reading of the limit is the
largest the program gives over the seeds, the upper the smallest the
control gives.  Needs a TPU; the benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    # correctness needs no warm-up: one event per analyst opens the window
    cell["mix"] = dict(cell["mix"], warmup_events=1)

    import jax

    if jax.devices()[0].platform != "tpu":
        run.log("no TPU")
        return 2
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, None, control=True)
        r = out["readings"]
        rows.append({"seed": seed, "program": r["max_rel_err"],
                     "control": r["control_max_rel_err"], "results": r["results"],
                     "correct": out["correct"], "failed": out["failed"],
                     "attempted": out["attempted"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": max(x["program"] for x in rows),
        "upper": min(x["control"] for x in rows),
        "seeds": [x["seed"] for x in rows],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
