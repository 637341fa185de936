"""Shared baselines for the dashboard benchmarks.

``NaiveExecutor`` mirrors what a DBMS does without factorization: materialize
the (filtered) denormalized join row-set, then hash-aggregate — cost grows
with the fact-table width × row count.  ``cold_engine`` is the paper's
``Factorized`` baseline: message passing but a cold message store per query.
"""

from __future__ import annotations

import numpy as np

from repro.core import CJTEngine, MessageStore, Query, jt_from_catalog
from repro.core import semiring as sr
from repro.relational.relation import Catalog


class NaiveExecutor:
    """Denormalize-then-aggregate (paper `Naive`)."""

    def __init__(self, catalog: Catalog, fact: str):
        self.catalog = catalog
        self.fact = fact

    def execute(self, q: Query, measure_col: str | None = None, op: str = "sum"):
        """Aggregate of ``q`` over the filtered wide table.

        ``op="sum"`` sums the measure (or counts rows without one);
        ``op="max"`` takes the largest measure per group, ``-inf`` for a
        group no row reaches (the tropical ring's 0̄).
        """
        cat = self.catalog
        fact = cat.get(self.fact, q.version_of(self.fact))
        n = fact.num_rows
        # 1) materialize the wide table: gather every dimension attribute
        cols: dict[str, np.ndarray] = {a: fact.codes[a] for a in fact.attrs}
        frontier = [self.fact]
        seen = {self.fact}
        while frontier:
            nxt = []
            for name in list(cat.names()):
                if name in seen or name in q.removed:
                    continue
                rel = cat.get(name, q.version_of(name))
                keys = [a for a in rel.attrs if a in cols]
                if not keys:
                    continue
                key = keys[0]
                # build key -> row index (dims are keyed by their first attr)
                idx = np.full(rel.domains[key], -1, np.int64)
                idx[rel.codes[key]] = np.arange(rel.num_rows)
                rows = idx[cols[key]]
                for a in rel.attrs:
                    if a not in cols:
                        cols[a] = rel.codes[a][rows]
                seen.add(name)
                nxt.append(name)
            if not nxt:
                break
        # 2) filters on the wide table
        mask = np.ones(n, bool)
        for p in q.predicates:
            mask &= p.mask[cols[p.attr]]
        # 3) aggregate
        if measure_col is None and q.measure is not None:
            measure_col = q.measure[1]
        vals = (
            cat.get(q.measure[0], q.version_of(q.measure[0])).measures[measure_col][
                : n
            ]
            if q.measure and q.measure[0] == self.fact
            else np.ones(n, np.float32)
        )
        dims = [self.catalog.domains()[a] for a in q.group_by]
        flat = (
            np.ravel_multi_index(tuple(cols[a].astype(np.int64) for a in q.group_by), dims)
            if q.group_by else np.zeros(n, np.int64)
        )
        if op == "max":
            out = np.full(int(np.prod(dims)), -np.inf)
            np.maximum.at(out, flat[mask], vals[mask])
        else:
            out = np.zeros(int(np.prod(dims)))
            np.add.at(out, flat, np.where(mask, vals, 0.0))
        return out.reshape(dims)


def cold_engine(catalog: Catalog, ring=sr.SUM, jt=None) -> CJTEngine:
    return CJTEngine(jt or jt_from_catalog(catalog), catalog, ring, store=MessageStore())
