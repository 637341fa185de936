"""The benchmark's plain reference: filter the joined table, then aggregate.

Independent of the program under test: it reads the tables the benchmark
generated from the seed (``bench.catalog.tables``), joins every dimension
table to the fact table once per run (the wide table), and answers each
viz with numpy in float64: the rows that pass every filter of the session,
except those the viz itself brushed (crossfilter), grouped by the viz's
attributes, then SUM of the measure, COUNT of rows, or MAX of the measure
(``-inf`` for a group no row reaches).

``precision="bfloat16"`` is the control: the measure values are rounded to
bfloat16 before they are aggregated, which is what a one-hot segment
reduction fed to the matrix unit at default precision would do to them.
"""

from __future__ import annotations

import numpy as np


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even)."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


class Reference:
    def __init__(self, cfg: dict, data: dict[str, dict], precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.doms = cfg["domains"]
        self.vizzes = {v["name"]: v for v in cfg["dashboard"]}
        fact = next(r for r in cfg["relations"] if "rows" in r)
        self.fact = fact["name"]
        cols = dict(data[self.fact]["codes"])
        joined = {self.fact}
        grew = True
        while grew:  # join every dimension reachable through a key column
            grew = False
            for rel in cfg["relations"]:
                name = rel["name"]
                key = next((a for a, c in rel["columns"].items() if c["dist"] == "key"), None)
                if name in joined or key is None or key not in cols:
                    continue
                codes = data[name]["codes"]
                row_of = np.full(self.doms[key], -1, np.int64)
                row_of[codes[key]] = np.arange(codes[key].size)
                rows = row_of[cols[key]]
                if (rows < 0).any():
                    raise ValueError(f"{self.fact}.{key} has codes missing from {name}")
                for a in rel["columns"]:
                    if a not in cols:
                        cols[a] = codes[a][rows]
                joined.add(name)
                grew = True
        self.cols = cols
        self.measures = {}
        for col, vals in data[self.fact]["measures"].items():
            vals = round_bf16(vals) if precision == "bfloat16" else vals
            self.measures[(self.fact, col)] = vals.astype(np.float64)
        self._masks: dict[tuple, np.ndarray] = {}
        self._flat: dict[str, np.ndarray] = {}

    def _row_mask(self, f: dict) -> np.ndarray:
        key = (f["attr"], f["lo"], f["hi"], tuple(f["values"]))
        hit = self._masks.get(key)
        if hit is None:
            allowed = np.zeros(self.doms[f["attr"]], bool)
            if f["values"]:
                allowed[list(f["values"])] = True
            else:
                allowed[f["lo"]:f["hi"]] = True
            hit = self._masks[key] = allowed[self.cols[f["attr"]]]
        return hit

    def answer(self, viz: str, filters: dict[str, dict]) -> np.ndarray:
        """The viz's aggregate under a session's filters (attr -> set event)."""
        v = self.vizzes[viz]
        n = next(iter(self.cols.values())).size
        mask = np.ones(n, bool)
        for f in filters.values():
            if f["source"] != viz:
                mask &= self._row_mask(f)
        shape = tuple(self.doms[a] for a in v["group_by"])
        flat = self._flat.get(viz)
        if flat is None:
            flat = np.zeros(n, np.int64)
            for a in v["group_by"]:
                flat = flat * self.doms[a] + self.cols[a]
            self._flat[viz] = flat
        flat = flat[mask]
        size = int(np.prod(shape))
        if v["ring"] == "count":
            return np.bincount(flat, minlength=size).astype(np.float64).reshape(shape)
        vals = self.measures[tuple(v["measure"])][mask]
        if v["ring"] == "sum":
            return np.bincount(flat, weights=vals, minlength=size).reshape(shape)
        if v["ring"] == "tropical_max":
            out = np.full(size, -np.inf)
            np.maximum.at(out, flat, vals)
            return out.reshape(shape)
        raise ValueError(f"no reference for ring {v['ring']!r}")
