"""The benchmark's reference against ``benchmarks.baselines.NaiveExecutor``
on small catalogs of both configurations."""

import numpy as np
import pytest

from bench import catalog, generator
from bench.tests import tiny
from bench.reference import Reference, round_bf16
from benchmarks.baselines import NaiveExecutor
from repro.core import Query
from repro.relational.relation import mask_in, mask_range


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_reference_matches_naive_executor(workload):
    cell = tiny.load(workload)
    cfg = cell["config"]
    data = catalog.tables(cfg, seed=2**32 + 3, scale=0.002)
    cat = catalog.catalog(cfg, data)
    ref = Reference(cfg, data)
    fact = next(r["name"] for r in cfg["relations"] if "rows" in r)
    naive = NaiveExecutor(cat, fact)
    gen = generator.Analyst(dict(cell["mix"], dims="all", step="jump"), cfg, 7, 0, 0)
    filters = {}
    for _ in range(12):
        ev = gen.next().event
        if ev["op"] == "clear":
            filters.pop(ev["attr"], None)
        else:
            filters[ev["attr"]] = ev
        for v in cfg["dashboard"]:
            preds = [
                mask_in(cfg["domains"][f["attr"]], f["values"], attr=f["attr"]) if f["values"]
                else mask_range(cfg["domains"][f["attr"]], f["lo"], f["hi"], attr=f["attr"])
                for f in filters.values() if f["source"] != v["name"]
            ]
            q = Query.make(cat, ring=v["ring"], group_by=v["group_by"], predicates=preds,
                           measure=tuple(v["measure"]) if v.get("measure") else None)
            want = naive.execute(q, op="max" if v["ring"] == "tropical_max" else "sum")
            got = ref.answer(v["name"], filters)
            np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-6)


def test_bf16_rounding_keeps_eight_significant_bits():
    x = np.array([1.0, 1.00390625, 1.005859375, 3.14159265, 1e-3, 123456.7], np.float32)
    y = round_bf16(x)
    assert (y.view(np.uint32) & 0xFFFF == 0).all()
    assert np.all(np.abs(y - x) <= np.abs(x) * 2.0**-8)
    assert y[0] == 1.0 and y[1] == 1.0  # a tie rounds to even
