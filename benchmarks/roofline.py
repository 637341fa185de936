"""Measured roofline for the CJT kernels → ``kernel_costs.json``.

Micro-benchmarks the three Pallas kernels the plan compiler can route to —
``segment_aggregate`` (sparse-bag ⊕ reduction), ``semiring_contract`` and
``tropical_contract`` (dense two-factor elimination) — against their jitted
lax/jnp reference implementations, on THIS machine and backend.  Three
numbers per kernel:

  launch_overhead_us   median wall time of a tile-sized call (fixed cost the
                       static gates were guessing at)
  bytes_per_sec        in+out bytes over wall time at the largest ladder size
  crossover_cost       largest one-hot-matmul work (N·G·V, resp. G·B·A) where
                       the kernel still beats the reference; geometric mean of
                       the last win and the first loss when they bracket

Derived knobs (the measured replacements for the old static gates):

  plan_kernel_cost          min over kernels of crossover_cost, floored at
                            the historical 1<<19 and capped at 1<<24 so a
                            noisy run can neither disable the kernel path nor
                            route pathological sizes to interpret mode
  calibration_union_budget  widening knee of segment_aggregate: the largest
                            segment count G where a fixed-N reduction still
                            runs within 2× of its G=64 time (widening a
                            calibration union is ~free up to there), clamped
                            to [64, 4096]

Outputs ``kernel_costs.json`` (machine-readable profile consumed by
``repro.kernels.costs``), ``roofline.md`` and ``roofline.csv`` to
``REPRO_BENCH_OUT`` (default: cwd).  Regenerate the committed default with

  PYTHONPATH=src REPRO_BENCH_OUT=benchmarks/baselines python -m benchmarks.roofline

``REPRO_PLAN_KERNEL_COST`` / ``REPRO_CALIBRATION_UNION_BUDGET`` env overrides
always win over the profile (see ``repro.core.plans``).
"""

from __future__ import annotations

import csv
import json
import os
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mode import resolve_interpret
from repro.kernels.segment_aggregate import ops as seg_ops
from repro.kernels.segment_aggregate.ref import segment_aggregate_ref
from repro.kernels.semiring_contract import ops as sc_ops
from repro.kernels.semiring_contract.ref import semiring_contract_ref
from repro.kernels.tropical_contract import ops as tc_ops
from repro.kernels.tropical_contract.ref import tropical_contract_ref

from .common import emit, seeded_rng, time_fn

INTERPRET = resolve_interpret(None)

COST_FLOOR = 1 << 19
COST_CAP = 1 << 24
BUDGET_LO, BUDGET_HI = 64, 4096

# (n, g, v) ladder for segment_aggregate: cost = n·g·v
SEG_LADDER = [(256, 64, 4), (1024, 64, 4), (4096, 64, 4),
              (16384, 64, 4), (32768, 128, 4)]
# (g, b, a) ladder for the dense contractions: cost = g·b·a
DENSE_LADDER = [(32, 32, 32), (64, 64, 64), (128, 128, 128),
                (256, 128, 128), (256, 256, 256)]
# G ladder for the union-budget knee (fixed n, v=1)
KNEE_N = 4096
KNEE_LADDER = [64, 128, 256, 512, 1024, 2048, 4096]


_seg_ref = jax.jit(segment_aggregate_ref, static_argnums=(2, 3))
_sc_ref = jax.jit(semiring_contract_ref)
_tc_ref = jax.jit(partial(tropical_contract_ref), static_argnums=(2,))


def _seg_data(n: int, g: int, v: int):
    rng = seeded_rng(f"roofline/seg/{n}/{g}/{v}")
    codes = jnp.asarray(rng.integers(0, g, n), jnp.int32)
    vals = jnp.asarray(rng.random((n, v)), jnp.float32)
    return codes, vals


def _dense_data(g: int, b: int, a: int):
    rng = seeded_rng(f"roofline/dense/{g}/{b}/{a}")
    m = jnp.asarray(rng.random((g, b)), jnp.float32)
    r = jnp.asarray(rng.random((b, a)), jnp.float32)
    return m, r


def _crossover(ladder: list[tuple[int, float, float]]) -> int:
    """Largest cost where the kernel wins; geomean with the first loss when
    the ladder brackets the flip.  All-win → top rung, all-lose → 0."""
    last_win = first_loss = None
    for cost, t_k, t_r in ladder:
        if t_k <= t_r:
            last_win = cost
        elif first_loss is None:
            first_loss = cost
    if last_win is None:
        return 0
    if first_loss is None or first_loss < last_win:
        return last_win
    return int((last_win * first_loss) ** 0.5)


def bench_segment_aggregate() -> dict:
    rows = []
    for n, g, v in SEG_LADDER:
        codes, vals = _seg_data(n, g, v)
        t_k, _ = time_fn(seg_ops.aggregate_op, codes, vals, g, op="sum",
                         interpret=INTERPRET)
        t_r, _ = time_fn(_seg_ref, codes, vals, g, "sum")
        rows.append((n * g * v, t_k, t_r))
    codes, vals = _seg_data(8, 8, 1)
    t0, _ = time_fn(seg_ops.aggregate_op, codes, vals, 8, op="sum",
                    interpret=INTERPRET, repeats=5)
    n, g, v = SEG_LADDER[-1]
    nbytes = 4 * (n + n * v + g * v)  # codes + values in, (g, v) out
    return {"launch_overhead_us": t0 * 1e6,
            "bytes_per_sec": nbytes / max(rows[-1][1], 1e-9),
            "crossover_cost": _crossover(rows),
            "ladder": [{"cost": c, "kernel_s": k, "ref_s": r}
                       for c, k, r in rows]}


def _bench_dense(op, ref) -> dict:
    rows = []
    for g, b, a in DENSE_LADDER:
        m, r = _dense_data(g, b, a)
        t_k, _ = time_fn(op, m, r)
        t_r, _ = time_fn(ref, m, r)
        rows.append((g * b * a, t_k, t_r))
    m, r = _dense_data(8, 8, 8)
    t0, _ = time_fn(op, m, r, repeats=5)
    g, b, a = DENSE_LADDER[-1]
    nbytes = 4 * (g * b + b * a + g * a)
    return {"launch_overhead_us": t0 * 1e6,
            "bytes_per_sec": nbytes / max(rows[-1][1], 1e-9),
            "crossover_cost": _crossover(rows),
            "ladder": [{"cost": c, "kernel_s": k, "ref_s": r}
                       for c, k, r in rows]}


def bench_union_knee() -> tuple[int, list[dict]]:
    """Largest G where widening a fixed-N segment reduction stays within 2×
    of its G=64 time — i.e. where launch/tile overhead still dominates and a
    union-carry calibration query widens for free."""
    rows, base = [], None
    for g in KNEE_LADDER:
        codes, vals = _seg_data(KNEE_N, g, 1)
        t, _ = time_fn(seg_ops.aggregate_op, codes, vals, g, op="sum",
                       interpret=INTERPRET)
        base = t if base is None else base
        rows.append({"num_segments": g, "kernel_s": t, "vs_g64": t / base})
    knee = BUDGET_LO
    for r in rows:
        if r["vs_g64"] <= 2.0:
            knee = max(knee, r["num_segments"])
    return min(max(knee, BUDGET_LO), BUDGET_HI), rows


def profile() -> dict:
    kernels = {
        "segment_aggregate": bench_segment_aggregate(),
        "semiring_contract": _bench_dense(
            partial(sc_ops.contract_op, interpret=INTERPRET), _sc_ref),
        "tropical_contract": _bench_dense(
            partial(tc_ops.contract_op, is_min=True, interpret=INTERPRET),
            lambda m, r: _tc_ref(m, r, True)),
    }
    budget, knee_rows = bench_union_knee()
    crossovers = [k["crossover_cost"] for k in kernels.values()]
    plan_cost = min(max(min(crossovers), COST_FLOOR), COST_CAP)
    return {
        "generated_by": "benchmarks.roofline",
        "backend": jax.default_backend(),
        "interpret": INTERPRET,
        "kernels": kernels,
        "union_knee": knee_rows,
        "derived": {
            "plan_kernel_cost": int(plan_cost),
            "calibration_union_budget": int(budget),
        },
    }


def render_md(prof: dict) -> str:
    out = ("# CJT kernel roofline (measured)\n\n"
           f"backend `{prof['backend']}`, interpret={prof['interpret']}\n\n"
           "| kernel | launch overhead µs | bytes/s | kernel-beats-ref up to cost |\n"
           "|---|---|---|---|\n")
    for name, k in prof["kernels"].items():
        out += (f"| {name} | {k['launch_overhead_us']:.1f} "
                f"| {k['bytes_per_sec']:.3e} | {k['crossover_cost']} |\n")
    d = prof["derived"]
    out += (f"\nderived: `plan_kernel_cost={d['plan_kernel_cost']}` "
            f"(floor {COST_FLOOR}, cap {COST_CAP}), "
            f"`calibration_union_budget={d['calibration_union_budget']}` "
            f"(clamped [{BUDGET_LO}, {BUDGET_HI}])\n")
    return out


def write_outputs(prof: dict, out_dir: Path) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "kernel_costs.json").write_text(
        json.dumps(prof, indent=2, sort_keys=True) + "\n")
    (out_dir / "roofline.md").write_text(render_md(prof))
    with open(out_dir / "roofline.csv", "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["kernel", "cost", "kernel_s", "ref_s"])
        for name, k in prof["kernels"].items():
            for row in k["ladder"]:
                w.writerow([name, row["cost"], row["kernel_s"], row["ref_s"]])


def main():
    prof = profile()
    for name, k in prof["kernels"].items():
        emit(f"roofline/{name}/launch_overhead", k["launch_overhead_us"] / 1e6)
        emit(f"roofline/{name}/crossover_cost_count", k["crossover_cost"] / 1e6,
             f"bytes/s={k['bytes_per_sec']:.3e}")
    d = prof["derived"]
    emit("roofline/derived/plan_kernel_cost_count", d["plan_kernel_cost"] / 1e6)
    emit("roofline/derived/calibration_union_budget_count",
         d["calibration_union_budget"] / 1e6)
    out_dir = Path(os.environ.get("REPRO_BENCH_OUT", "."))
    write_outputs(prof, out_dir)
    print(f"# wrote {out_dir / 'kernel_costs.json'} "
          f"(+ roofline.md, roofline.csv)", flush=True)


if __name__ == "__main__":
    main()
