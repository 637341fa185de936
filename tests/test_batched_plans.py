"""Vmapped batch absorption + speculative σ prefetch: the metamorphic suite.

The batched fan-out's correctness spine is *metamorphic parity*: executing N
sibling absorptions through one vmapped compiled plan must be **bit-identical**
to executing them one by one — across every ring (SUM/COUNT/MIN/MAX/MOMENTS),
across batch widths that do and do not divide evenly into groups, with
heterogeneous γ domains (the ⊕-identity padding path) and with the plan cache
on or off (batching degrades to the sequential reference path).  Measures are
small integers, exactly representable in f32, so every summation order yields
the same bits (same convention as tests/test_plans.py).

The speculative-prefetch property: after ``Session.idle(speculate=k)``, a
``SetFilter`` to *any* prefetched σ value returns results digest-equal to a
cold engine while executing nothing — no store probes, no plan dispatches.

Plus the Session GC regression (ROADMAP): open-close cycles must not grow the
``MessageStore`` or leak pins.
"""

import hashlib

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import (
    CJTEngine,
    DashboardSpec,
    MessageStore,
    Query,
    SetFilter,
    Treant,
    VizSpec,
    jt_from_catalog,
    speculate_filters,
)
from repro.core import semiring as sr
from repro.relational.relation import Catalog, Relation, mask_in

N_FACT = 600  # > one 512-row kernel tile → exercises row padding


def star_catalog(n_fact: int = N_FACT, seed: int = 0) -> Catalog:
    """F(a,b)+m ← S(b,c), T(a,d), U(b,e).  Mixed γ domains (10/5/9) exercise
    the batch-padding path; integer measures keep f32 sums bitwise-stable."""
    rng = np.random.default_rng(seed)
    doms = {"a": 13, "b": 7, "c": 10, "d": 5, "e": 9}

    def codes(attrs, n):
        return {x: rng.integers(0, doms[x], n).astype(np.int32) for x in attrs}

    f = Relation("F", ("a", "b"), codes(("a", "b"), n_fact), doms,
                 measures={"m": rng.integers(0, 16, n_fact).astype(np.float32)})
    s = Relation("S", ("b", "c"), codes(("b", "c"), 77), doms)
    t = Relation("T", ("a", "d"), codes(("a", "d"), 29), doms)
    u = Relation("U", ("b", "e"), codes(("b", "e"), 41), doms)
    return Catalog([f, s, t, u])


RINGS = {
    "count": sr.COUNT,
    "sum": sr.SUM,
    "tropical_min": sr.TROPICAL_MIN,
    "tropical_max": sr.TROPICAL_MAX,
    "moments": sr.MOMENTS,
}


def assert_factors_identical(f1, f2):
    assert f1.attrs == f2.attrs
    l1 = jax.tree_util.tree_leaves(f1.field)
    l2 = jax.tree_util.tree_leaves(f2.field)
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def digest_factor(f) -> str:
    h = hashlib.sha1()
    for leaf in jax.tree_util.tree_leaves(f.field):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# metamorphic parity: batched ≡ sequential, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", sorted(RINGS))
@pytest.mark.parametrize("width", [2, 3, 5])
def test_batched_parity_rings_and_widths(ring_name, width):
    """Same-γ siblings differing only in σ masks: every ring, widths that do
    (2) and don't (3, 5) tile evenly against the σ attr's domain."""
    cat = star_catalog(seed=width)
    jt = jt_from_catalog(cat)
    measure = None if ring_name == "count" else ("F", "m")
    base = Query.make(cat, ring=ring_name, measure=measure, group_by=("c",))
    qs = [base.with_predicate(mask_in(5, [i % 5], attr="d")) for i in range(width)]
    seq = CJTEngine(jt, cat, RINGS[ring_name], use_plans=True)
    bat = CJTEngine(jt, cat, RINGS[ring_name], use_plans=True)
    want = [seq.execute(q) for q in qs]
    got = bat.execute_many(qs)
    for (fw, _), (fg, sg) in zip(want, got):
        assert_factors_identical(fw, fg)
    assert bat.plans.stats.batched_absorptions >= 2
    assert bat.plans.stats.batch_width >= 2


@pytest.mark.parametrize("ring_name", sorted(RINGS))
def test_batched_parity_heterogeneous_gamma_padding(ring_name):
    """Siblings carrying *different* γ attrs (domains 10/5/9/7) batch through
    placeholder canonicalization + ⊕-identity padding — still bit-identical."""
    cat = star_catalog(seed=11)
    jt = jt_from_catalog(cat)
    measure = None if ring_name == "count" else ("F", "m")
    base = Query.make(cat, ring=ring_name, measure=measure)
    pred = mask_in(13, [0, 2, 5, 7], attr="a")
    qs = [base.with_group_by(g).with_predicate(pred) for g in ("c", "d", "e", "b")]
    seq = CJTEngine(jt, cat, RINGS[ring_name], use_plans=True)
    bat = CJTEngine(jt, cat, RINGS[ring_name], use_plans=True)
    # warm the base CJTs (the dashboard offline stage): every root converges
    # on the σ'd bag and the four absorptions share one batch signature
    for q in qs:
        seq.calibrate(q.without_predicate("a"))
        bat.calibrate(q.without_predicate("a"))
    want = [seq.execute(q) for q in qs]
    got = bat.execute_many(qs)
    for (fw, _), (fg, _) in zip(want, got):
        assert_factors_identical(fw, fg)
    assert bat.plans.stats.batched_absorptions >= 2


@pytest.mark.parametrize("use_plans", [False, True])
def test_batched_parity_plans_on_off(use_plans):
    """execute_many must agree bit-for-bit with the un-jitted reference
    engine whether the plan cache (and hence batching) is on or off."""
    cat = star_catalog(seed=17)
    jt = jt_from_catalog(cat)
    base = Query.make(cat, ring="sum", measure=("F", "m"))
    qs = [
        base.with_group_by("c").with_predicate(mask_in(5, [1, 3], attr="d")),
        base.with_group_by("d").with_predicate(mask_in(5, [1, 3], attr="d")),
        base.with_group_by("e").with_predicate(mask_in(5, [1, 3], attr="d")),
    ]
    ref = CJTEngine(jt, cat, sr.SUM, use_plans=False)
    eng = CJTEngine(jt, cat, sr.SUM, use_plans=use_plans)
    for q in qs:  # warm both so the batched engine's roots converge
        ref.calibrate(q.without_predicate("d"))
        eng.calibrate(q.without_predicate("d"))
    want = [ref.execute(q) for q in qs]
    got = eng.execute_many(qs)
    for (fw, _), (fg, _) in zip(want, got):
        assert_factors_identical(fw, fg)
    if use_plans:
        assert eng.plans.stats.batched_execs >= 1
    else:
        assert eng.plans is None  # batching inert, sequential fallback


def test_batched_execstats_counters():
    cat = star_catalog(seed=23)
    jt = jt_from_catalog(cat)
    base = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    qs = [base.with_predicate(mask_in(5, [i], attr="d")) for i in range(3)]
    eng = CJTEngine(jt, cat, sr.SUM, use_plans=True)
    results = eng.execute_many(qs)
    batched = [s for _, s in results if s.batched_absorptions]
    assert len(batched) >= 2
    assert all(s.batch_width >= 2 for s in batched)
    assert eng.plans.stats.batched_execs >= 1
    assert eng.plans.stats.batch_width == max(s.batch_width for s in batched)


def test_batched_plan_retrace_only_on_new_structure():
    """Re-brushing the same batch signature (new masks) must re-execute the
    cached vmapped plan — zero new traces, like the scalar plans."""
    cat = star_catalog(seed=29)
    jt = jt_from_catalog(cat)
    base = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    eng = CJTEngine(jt, cat, sr.SUM, use_plans=True)
    eng.execute_many([base.with_predicate(mask_in(5, [i], attr="d")) for i in (0, 1)])
    built = eng.plans.stats.plans_built
    out = eng.execute_many(
        [base.with_predicate(mask_in(5, [i], attr="d")) for i in (2, 4)]
    )
    assert eng.plans.stats.plans_built == built
    assert all(s.plan_hits > 0 or s.messages_reused > 0 for _, s in out)


# ---------------------------------------------------------------------------
# session-level: batched fan-out ≡ per-viz dispatch
# ---------------------------------------------------------------------------

def star_spec() -> DashboardSpec:
    return DashboardSpec(vizzes=(
        VizSpec("by_a", measure=("F", "m"), ring="sum", group_by=("a",)),
        VizSpec("by_c", measure=("F", "m"), ring="sum", group_by=("c",)),
        VizSpec("by_d", measure=("F", "m"), ring="sum", group_by=("d",)),
        VizSpec("by_e", measure=("F", "m"), ring="sum", group_by=("e",)),
    ))


def test_session_fanout_batched_vs_unbatched_bit_identical():
    cat = star_catalog(seed=31)
    jt = jt_from_catalog(cat)
    tb = Treant(cat, ring=sr.SUM, jt=jt, use_plans=True, batch_fanout=True)
    tu = Treant(cat, ring=sr.SUM, jt=jt, use_plans=True, batch_fanout=False)
    sb = tb.open_session(star_spec(), name="b")
    su = tu.open_session(star_spec(), name="u")
    events = [
        SetFilter("a", values=(0, 1), source="by_a"),
        SetFilter("a", values=(3,), source="by_a"),
        SetFilter("b", values=(2, 4)),
    ]
    for ev in events:
        rb, ru = sb.apply(ev), su.apply(ev)
        assert rb.affected == ru.affected
        for viz in rb.affected:
            assert_factors_identical(
                rb.results[viz].factor, ru.results[viz].factor
            )
    assert tb.cache_stats()["plans"]["batched_absorptions"] > 0
    assert tu.cache_stats()["plans"]["batched_absorptions"] == 0
    assert tb.cache_stats()["plans"]["batch_width"] >= 2


# ---------------------------------------------------------------------------
# speculative σ prefetch
# ---------------------------------------------------------------------------

def test_speculate_filters_shapes():
    ev = SetFilter("x", lo=4, hi=8)
    cands = speculate_filters(ev, 20, 3)
    assert [(c.lo, c.hi) for c in cands] == [(8, 12), (0, 4), (12, 16)]
    # clipped at the domain edge, deduped, deterministic
    cands = speculate_filters(SetFilter("x", lo=0, hi=8), 10, 4)
    assert [(c.lo, c.hi) for c in cands] == [(8, 10)]
    ev = SetFilter("x", values=(2, 3))
    cands = speculate_filters(ev, 10, 4)
    assert [c.values for c in cands] == [(4, 5), (0, 1), (6, 7), (8, 9)]
    assert all(c.attr == "x" and c.source == ev.source for c in cands)


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_prefetched_rebrush_is_pure_hit(seed):
    """After idle(speculate=k): a SetFilter to ANY prefetched σ value returns
    results digest-equal to a cold engine, with zero messages computed, zero
    plan executions and zero store misses — pure prefetch-cache hits."""
    rng = np.random.default_rng(seed)
    cat = star_catalog(n_fact=400, seed=seed % 7)
    jt = jt_from_catalog(cat)
    t = Treant(cat, ring=sr.SUM, jt=jt, use_plans=True, batch_fanout=True)
    sess = t.open_session(star_spec(), name="s")
    attr, dom = ("a", 13) if rng.integers(2) else ("b", 7)
    lo = int(rng.integers(0, dom - 1))
    ev = SetFilter(attr, lo=lo, hi=int(rng.integers(lo + 1, dom + 1)),
                   source="by_a")
    sess.apply(ev)
    sess.idle(speculate=2)
    cands = speculate_filters(ev, dom, 2)
    assert cands and sess.stats()["prefetched"] > 0
    cand = cands[int(rng.integers(len(cands)))]
    st0 = t.cache_stats()
    res = sess.apply(cand)
    st1 = t.cache_stats()
    assert res.affected  # the re-brush really changed the linked vizzes
    for viz in res.affected:
        s = res.results[viz].stats
        assert s.prefetch_hits == 1 and s.messages_computed == 0
        cold = CJTEngine(jt, cat, sr.SUM, store=MessageStore(), use_plans=True)
        f_cold, _ = cold.execute(sess.query_of(viz))
        assert digest_factor(res.results[viz].factor) == digest_factor(f_cold)
    plan_execs = lambda st_: st_["plans"]["plans_built"] + st_["plans"]["plan_hits"]
    assert plan_execs(st1) == plan_execs(st0), "re-brush executed a plan"
    assert st1["misses"] == st0["misses"] and st1["hits"] == st0["hits"]


def test_speculation_counts_and_capacity():
    cat = star_catalog(seed=41)
    t = Treant(cat, ring=sr.SUM, use_plans=True)
    sess = t.open_session(star_spec(), name="s")
    sess.prefetch_capacity = 4
    sess.apply(SetFilter("a", values=(1, 2), source="by_a"))
    sess.idle(speculate=3)
    st_ = sess.stats()
    assert st_["speculative_queries_total"] > 0
    assert 0 < st_["prefetched"] <= 4
    assert t.scheduler.stats()["speculative_queries"] == st_["speculative_queries_total"]


# ---------------------------------------------------------------------------
# Session GC (ROADMAP): close unpins and drops producer-tagged entries
# ---------------------------------------------------------------------------

def test_session_close_gc_two_cycles_store_stable():
    """Two open-close cycles (each brushing a *different* σ value) must not
    grow the MessageStore: close unpins the base CJTs and evicts the
    session-produced interaction messages, so only the shared offline
    calibration survives."""
    cat = star_catalog(seed=43)
    t = Treant(cat, ring=sr.SUM, use_plans=True)
    sizes, pinned = [], []
    for i in range(2):
        sess = t.open_session(star_spec())
        sess.apply(SetFilter("a", values=(i,), source="by_a"))
        sess.idle()
        sess.apply(SetFilter("b", values=(i, i + 1)))
        sess.idle(speculate=1)
        sess.close()
        sizes.append(len(t.store))
        pinned.append(len(t.store._pinned))
        assert t.scheduler.pending(sess.id) == 0
    assert sizes[1] <= sizes[0], f"store grew across sessions: {sizes}"
    assert pinned == [0, 0], "close leaked pins"
    assert t.cache_stats()["sessions"] == 0


def test_fallback_update_releases_pins_before_version_bump():
    """A delta the ring cannot absorb (MIN delete) migrates no pins, but the
    base queries are version-bumped: the old-version pins must be released
    during the update — a later close() only knows the bumped sigs and would
    otherwise leak them forever (unevictable store entries)."""
    cat = star_catalog(seed=59)
    t = Treant(cat, ring=sr.TROPICAL_MIN, use_plans=True)
    spec = DashboardSpec(vizzes=(
        VizSpec("by_c", measure=("F", "m"), ring="tropical_min", group_by=("c",)),
    ))
    sess = t.open_session(spec)
    assert t.store._pinned
    mask = np.zeros(cat.get("F").num_rows, bool)
    mask[:5] = True
    new_rel, delta = cat.get("F").delete_rows(mask)
    res = t.update(new_rel, delta)
    assert res.queries_fallback > 0
    sess.close()
    assert not t.store._pinned, "fallback update leaked old-version pins"


def test_idle_budget_gates_speculation():
    cat = star_catalog(seed=61)
    t = Treant(cat, ring=sr.SUM, use_plans=True)
    sess = t.open_session(star_spec(), name="s")
    sess.apply(SetFilter("a", values=(1,), source="by_a"))
    # exhausted message budget: calibration consumed it all, no speculation
    sess.idle(budget_messages=1, speculate=2)
    assert sess.stats()["prefetched"] == 0
    # slack budget: speculation runs
    sess.idle(speculate=2)
    assert sess.stats()["prefetched"] > 0


def test_clear_and_undo_invalidate_speculation_anchor():
    from repro.core import ClearFilter, Undo

    cat = star_catalog(seed=67)
    t = Treant(cat, ring=sr.SUM, use_plans=True)
    sess = t.open_session(star_spec(), name="s", calibrate=False)
    sess.apply(SetFilter("a", values=(1,), source="by_a"))
    sess.apply(ClearFilter("a"))
    sess.idle(speculate=2)  # no anchor: must not re-insert the cleared σ
    assert sess.stats()["prefetched"] == 0
    sess.apply(SetFilter("b", values=(2,)))
    sess.apply(Undo())      # brush undone → anchor dropped with it
    sess.idle(speculate=2)
    assert sess.stats()["prefetched"] == 0


def test_close_keeps_other_sessions_pins():
    cat = star_catalog(seed=47)
    t = Treant(cat, ring=sr.SUM, use_plans=True)
    s1 = t.open_session(star_spec(), name="s1")
    s2 = t.open_session(star_spec(), name="s2")
    s1.apply(SetFilter("a", values=(0,), source="by_a"))
    s1.close()
    # s2 pinned the same base signatures: they must survive s1's GC
    assert t.store._pinned, "shared pins dropped by sibling close"
    for v in ("by_a", "by_c", "by_d", "by_e"):
        assert t.engine.is_calibrated(s2.query_of(v))
    s2.close()
    assert not t.store._pinned


# ---------------------------------------------------------------------------
# env gates (CI matrix)
# ---------------------------------------------------------------------------

def test_env_gates_use_plans_and_batch_fanout(monkeypatch):
    cat = star_catalog(seed=53)
    monkeypatch.setenv("REPRO_USE_PLANS", "0")
    monkeypatch.setenv("REPRO_BATCH_FANOUT", "0")
    t = Treant(cat, ring=sr.SUM)
    assert t.engine.plans is None and not t.batch_fanout
    assert "plans" not in t.cache_stats()
    monkeypatch.setenv("REPRO_USE_PLANS", "1")
    monkeypatch.setenv("REPRO_BATCH_FANOUT", "1")
    t = Treant(cat, ring=sr.SUM)
    assert t.engine.plans is not None and t.batch_fanout
    # explicit arguments always win over the env
    t = Treant(cat, ring=sr.SUM, use_plans=False, batch_fanout=False)
    assert t.engine.plans is None and not t.batch_fanout


# ---------------------------------------------------------------------------
# lane-dense rowwise stage ≡ the row-major ``take`` path: vmapped and fused
# ---------------------------------------------------------------------------

from test_plans import (  # noqa: E402 — the plan-level take reference
    LANE_DOMS,
    LANE_IN,
    LANE_OUT,
    LANE_REL,
    LANE_RINGS,
    assert_lane_equal,
    lane_args,
    lane_device,
    lane_member,
    lane_rows,
    take_reference,
)

# members' carried γ sizes: the group pads x and y to the widest
LANE_MEMBERS = ({"x": 6, "y": 4}, {"x": 3, "y": 2}, {"x": 5, "y": 4})


def lane_group(ring_name, codes, sigma, width, seed):
    """``width`` members' host inputs, device arguments and take references."""
    dims = [dict(LANE_DOMS, **md) for md in LANE_MEMBERS[:width]]
    members = [lane_member(ring_name, codes, d, sigma, seed + i) for i, d in enumerate(dims)]
    dev = [lane_device(*mb) for mb in members]
    in_idx, pred_codes, seg = lane_args(codes, sigma)
    args = (tuple(d[0] for d in dev), tuple(d[1] for d in dev), in_idx,
            tuple(d[2] for d in dev), pred_codes, seg)
    wants = [take_reference(ring_name, codes, d, mb[0], mb[1], sigma, mb[2])
             for d, mb in zip(dims, members)]
    return args, wants


@pytest.mark.parametrize("sigma", [(), ("b",)])
@pytest.mark.parametrize("width", [2, 3])
@pytest.mark.parametrize("ring_name", sorted(LANE_RINGS))
def test_lane_dense_batched_plan_matches_take(ring_name, width, sigma):
    """B = 2 and 3 vmapped members with padded γ: every intermediate is
    (B, *cells, N), and each member's slice equals its take reference."""
    from repro.core import plans as plans_mod

    n = 1024
    codes = lane_rows(n, seed=width)
    args, wants = lane_group(ring_name, codes, sigma, width, seed=10)
    plan = plans_mod._build_batched_sparse_plan(
        LANE_RINGS[ring_name], LANE_REL, LANE_DOMS, LANE_IN, sigma, LANE_OUT, n,
        LANE_MEMBERS[:width],
    )
    outs = plan.fn(*args)
    assert len(outs) == width
    for out, want in zip(outs, wants):
        assert_lane_equal(out, want)
    # vmapped, every gather is wider than one column: σ masks go one-hot in
    # every ring, messages only where 0̄ is 0 (the tropical 0̄ is -inf)
    onehot, take = plan.gathers
    assert onehot + take == 3 + len(sigma)
    assert take == (3 if ring_name == "tropical_max" else 0)


@pytest.mark.parametrize("ring_name", sorted(LANE_RINGS))
def test_lane_dense_level_plan_matches_take(ring_name):
    """A level-fused plan of a two-member group and a one-member group with
    σ: both hand lane-major slabs to one fused segment reduction."""
    from repro.core import plans as plans_mod

    n = 1024
    codes = lane_rows(n, seed=21)
    ring = LANE_RINGS[ring_name]
    args2, wants2 = lane_group(ring_name, codes, (), 2, seed=22)
    args1, wants1 = lane_group(ring_name, codes, ("c",), 1, seed=24)
    statics = (
        (LANE_REL, LANE_DOMS, LANE_IN, (), LANE_OUT, n, LANE_MEMBERS[:2]),
        (LANE_REL, LANE_DOMS, LANE_IN, ("c",), LANE_OUT, n, LANE_MEMBERS[:1]),
    )
    plan = plans_mod._build_level_plan(ring, statics)
    assert plan.fused_messages == 3
    outs = plan.fn((args2, args1))
    for got, wants in zip(outs, (wants2, wants1)):
        for out, want in zip(got, wants):
            assert_lane_equal(out, want)
    assert len(plan.group_gathers) == 2


def test_rowwise_gather_counters_per_member():
    """``PlanStats`` counts each executed member's gathers from its plan's
    static count, as (one-hot, take): the same queries over the tropical
    ring, whose messages hold 0̄ = -inf, move the message gathers of the
    vmapped members from one-hot to take."""
    cat = star_catalog(seed=31)
    jt = jt_from_catalog(cat)
    counts = {}
    for ring_name in ("sum", "tropical_max"):
        base = Query.make(cat, ring=ring_name, measure=("F", "m"), group_by=("c",))
        qs = [base.with_predicate(mask_in(5, [i], attr="d")) for i in range(3)]
        eng = CJTEngine(jt, cat, RINGS[ring_name], use_plans=True)
        eng.execute_many(qs)
        st = eng.plans.stats
        assert st.batched_absorptions == 3
        counts[ring_name] = (st.onehot_gathers, st.take_gathers)
    (o_sum, t_sum), (o_max, t_max) = counts["sum"], counts["tropical_max"]
    assert o_sum + t_sum == o_max + t_max
    assert o_sum >= 3 and o_max == 0 and t_max == t_sum + o_sum


def test_rowwise_gather_counters_in_cache_stats():
    cat = star_catalog(seed=31)
    t = Treant(cat, ring=sr.SUM, jt=jt_from_catalog(cat), use_plans=True)
    sess = t.open_session(star_spec(), name="s")
    sess.apply(SetFilter("a", values=(0, 1), source="by_a"))
    plans = t.cache_stats()["plans"]
    assert plans["batched_absorptions"] > 0 and plans["onehot_gathers"] > 0
    assert plans["take_gathers"] >= 0


def test_rowwise_onehot_pct_reads_the_gather_counters():
    """The benchmark's reader: one-hot share of the window's gathers, and
    nothing for a program without the counters or with no gathers."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "metrics" / "rowwise_onehot_pct.py"
    spec = importlib.util.spec_from_file_location("rowwise_onehot_pct", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    counters = {"plans.onehot_gathers": 30, "plans.take_gathers": 10}
    assert reader.read({"counters": counters}) == 75.0
    assert reader.read({"counters": {"plans.plan_hits": 5}}) is None
    assert reader.read({"counters": {"plans.onehot_gathers": 0,
                                     "plans.take_gathers": 0}}) is None
