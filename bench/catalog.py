"""Build a configuration's tables and dashboard from its JSON file.

A configuration file (``bench/configs/<name>.json``) states the deployment:
its relations with row counts, the distribution of every code column and
measure, the attribute domains, the dashboard's vizzes, the dimensions an
analyst may filter, and the think-time policy the server runs.  This module
is the one general builder that reads such a file; it has no branch on a
configuration's name.

Column generators (``"dist"``):

- ``key``: ``arange(rows)`` (a dimension table's key; ``rows`` defaults to
  the key's domain)
- ``uniform``: uniform codes over the attribute's domain
- ``zipf``: code ``r`` drawn with probability proportional to
  ``1 / (r + 1) ** s`` (code 0 is the hottest)
- ``floor_mod``: ``((arange(rows) + add) // div) % mod``, for calendar
  attributes such as month and day of week

Measure generators: ``gamma`` (``shape``, ``scale``).

Every table is drawn on the host with vectorized numpy from one
``numpy.random.Generator``, in the file's order, so a seed gives the same
tables on every machine.
"""

from __future__ import annotations


import numpy as np

from repro.core import DashboardSpec, VizSpec
from repro.relational.relation import Catalog, Relation


def _codes(spec: dict, rows: int, domain: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "key":
        return np.arange(rows)
    if dist == "uniform":
        return rng.integers(0, domain, rows)
    if dist == "zipf":
        p = 1.0 / np.arange(1, domain + 1, dtype=np.float64) ** float(spec["s"])
        cdf = np.cumsum(p / p.sum())
        return np.minimum(np.searchsorted(cdf, rng.random(rows)), domain - 1)
    if dist == "floor_mod":
        return ((np.arange(rows) + int(spec.get("add", 0))) // int(spec["div"])) % int(spec["mod"])
    raise ValueError(f"unknown code distribution {dist!r}")


def _measure(spec: dict, rows: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "gamma":
        return rng.gamma(float(spec["shape"]), float(spec["scale"]), rows)
    raise ValueError(f"unknown measure distribution {dist!r}")


def tables(cfg: dict, seed: int, scale: float = 1.0) -> dict[str, dict]:
    """Every relation as ``{"codes": {attr: int32}, "measures": {col: float32}}``.

    ``scale`` shrinks the fact tables (relations with an explicit ``rows``)
    for tests on the CPU; dimension tables keep their key domains.
    """
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    doms = cfg["domains"]
    out = {}
    for rel in cfg["relations"]:
        cols = rel["columns"]
        if "rows" in rel:
            rows = max(1, int(round(rel["rows"] * scale)))
        else:
            key = next(a for a, c in cols.items() if c["dist"] == "key")
            rows = doms[key]
        out[rel["name"]] = {
            "codes": {a: _codes(c, rows, doms[a], rng).astype(np.int32)
                      for a, c in cols.items()},
            "measures": {m: _measure(s, rows, rng).astype(np.float32)
                         for m, s in rel.get("measures", {}).items()},
        }
    return out


def catalog(cfg: dict, data: dict[str, dict]) -> Catalog:
    doms = cfg["domains"]
    return Catalog([
        Relation(
            name=rel["name"], attrs=tuple(rel["columns"]),
            codes=data[rel["name"]]["codes"], domains=dict(doms),
            measures=data[rel["name"]]["measures"],
        )
        for rel in cfg["relations"]
    ])


def dashboard(cfg: dict) -> DashboardSpec:
    return DashboardSpec(vizzes=tuple(
        VizSpec(
            v["name"], ring=v["ring"], group_by=tuple(v["group_by"]),
            measure=tuple(v["measure"]) if v.get("measure") else None,
        )
        for v in cfg["dashboard"]
    ))
