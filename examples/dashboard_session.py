"""A multi-viz crossfilter dashboard session over the Flight schema, driven
by typed interaction events with think-time calibration between them
(paper §4.2.1; Mosaic-style linked selection).

Four linked vizzes share one engine/message store: brushing the carrier bar
chart fans a SetFilter out to the other three, whose re-renders reuse each
other's materialized messages.  ``Session.idle`` spends simulated user
think-time on the shared scheduler, so the next brush is a few dimension-bag
absorptions instead of full message passing.

    PYTHONPATH=src python examples/dashboard_session.py
"""

import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.baselines import NaiveExecutor  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    DashboardSpec, Drill, SetFilter, Treant, Undo, VizSpec, jt_from_catalog,
)
from repro.core import semiring as sr  # noqa: E402
from repro.relational import schema  # noqa: E402


def main():
    cat = schema.flight(n_flights=100_000)
    jt = jt_from_catalog(cat)
    treant = Treant(cat, ring=sr.SUM, jt=jt)
    naive = NaiveExecutor(cat, "Flights")

    spec = DashboardSpec(vizzes=(
        VizSpec("delay_map", measure=("Flights", "dep_delay"), ring="sum",
                group_by=("airport_state",)),
        VizSpec("monthly", measure=("Flights", "dep_delay"), ring="sum",
                group_by=("month",)),
        VizSpec("by_size", measure=("Flights", "dep_delay"), ring="sum",
                group_by=("airport_size",)),
        VizSpec("carrier_bar", measure=("Flights", "dep_delay"), ring="sum",
                group_by=("carrier_group",)),
    ))
    t0 = time.perf_counter()
    sess = treant.open_session(spec, name="anna")
    print(f"[offline] calibrated 4 linked vizzes in {time.perf_counter()-t0:.2f}s")

    events = [
        ("brush carriers 0-1", SetFilter("carrier_group", values=(0, 1),
                                         source="carrier_bar")),
        ("re-brush carriers 2-3", SetFilter("carrier_group", values=(2, 3),
                                            source="carrier_bar")),
        ("brush big airports", SetFilter("airport_size", values=(2, 3),
                                         source="by_size")),
        ("drill monthly by dow", Drill("monthly", "dow")),
        ("undo the drill", Undo()),
    ]
    for label, ev in events:
        res = sess.apply(ev)
        t_naive = 0.0
        ok = True
        for viz in res.affected:
            q = sess.query_of(viz)
            t1 = time.perf_counter()
            r_naive = naive.execute(q)
            t_naive += time.perf_counter() - t1
            ok &= np.allclose(np.asarray(res.results[viz].factor.field).ravel().sum(),
                              np.asarray(r_naive).sum(), rtol=1e-3)
        print(f"[online] {label:22s} {len(res.affected)} vizzes re-rendered "
              f"naive={t_naive*1e3:7.1f}ms treant={res.latency_s*1e3:6.1f}ms "
              f"({t_naive/max(res.latency_s,1e-9):5.0f}x) match={ok}")
        # user thinks; the scheduler calibrates the affected vizzes' CJTs
        n = sess.idle(budget_seconds=2.0)
        print(f"         think-time: {n} messages calibrated "
              f"(pending={sess.stats()['pending_calibrations']})")
    print("[session]", sess.stats())
    print("[cache]", treant.cache_stats())


if __name__ == "__main__":
    enable_compile_cache()
    main()
