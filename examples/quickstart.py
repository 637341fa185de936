"""Quickstart: build a CJT over a star schema, run interaction queries.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Query, Treant, jt_from_catalog
from repro.core import semiring as sr
from repro.relational import schema
from repro.relational.relation import mask_in
from repro.relational.sql import parse


def main():
    # 1. the data engineer's offline stage: join graph + dashboard queries
    cat = schema.salesforce(n_opp=50_000)
    jt = jt_from_catalog(cat)
    print("join tree bags:", sorted(jt.bags))

    treant = Treant(cat, ring=sr.SUM, jt=jt)
    total = Query.make(cat, ring="sum", measure=("Opp", "amount"))
    pie = total.with_group_by("camp_type")
    treant.register_dashboard("pipeline_total", total)
    treant.register_dashboard("pipeline_by_campaign", pie)
    print("offline calibration done:", treant.cache_stats())

    # 2. the domain user's online stage: widgets → interaction queries
    d = cat.domains()
    q1 = pie.with_predicate(mask_in(d["role_name"], [1], attr="role_name",
                                    label="Role = Sales Associate"))
    res = treant.interact("anna", "pipeline_by_campaign", q1)
    print(f"filter by role: {res.latency_s*1e3:.1f}ms, "
          f"messages computed={res.stats.messages_computed} "
          f"reused={res.stats.messages_reused}")
    print("  pipeline by campaign type:", np.asarray(res.factor.field).round(0)[:5])

    # think-time: calibrate the latest query in the background
    n = treant.think_time("anna", "pipeline_by_campaign")
    print(f"think-time calibration materialized {n} messages")

    # 3. next interaction builds on the previous one — and on its CJT
    q2 = q1.add_group_by("title")
    res2 = treant.interact("anna", "pipeline_by_campaign", q2)
    print(f"add group-by title: {res2.latency_s*1e3:.1f}ms, "
          f"computed={res2.stats.messages_computed} reused={res2.stats.messages_reused}")

    # 4. the SQL face of the middleware
    q3 = parse("SELECT camp_type, SUM(amount) FROM Opp WHERE state IN (1,2,3) "
               "GROUP BY camp_type", cat)
    res3 = treant.interact("anna", "pipeline_by_campaign", q3)
    print(f"SQL interaction: {res3.latency_s*1e3:.1f}ms  "
          f"result[:4]={np.asarray(res3.factor.field)[:4].round(0)}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
