"""Level-fused kernel launches: the metamorphic suite for ISSUE 7.

With ``fuse_level_kernel`` on (and plans + level batching on), every
calibration level executes as ONE host dispatch whose kernel-eligible batch
groups share a single multi-segment Pallas launch.  The fused pass must leave
the MessageStore **bit-identical** to the sequential per-edge reference loop —
across rings (COUNT/SUM/MIN/MAX/MOMENTS), tree shapes (chain/star/bushy) and
plans on/off (plans off → fusion inert, per-edge loop).  Measures are small
integers exactly representable in f32, so every ⊕-order yields the same bits
(same convention as tests/test_level_calibration.py, whose catalogs this
reuses).

Plus: the dispatch-counter bound the bench gate relies on
(``calibration_dispatches ≤ levels``), the fused-launch counters, the
``REPRO_FUSE_LEVEL_KERNEL`` env gate, MOMENTS stacked-leaf kernel ≡ lax
parity, the measured-cost-profile resolution chain
(``repro.kernels.costs``), and the ``cache_stats`` MAX_FIELDS aggregation
regression (satellite 6).
"""

import json

import jax
import numpy as np
import pytest

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import CJTEngine, MessageStore, Query, Treant, jt_from_catalog
from repro.core import plans as plans_mod
from repro.core import semiring as sr
from repro.core.plans import PlanStats
from repro.kernels import costs as kernel_costs
from repro.relational.relation import mask_in

from test_level_calibration import (  # same rootdir, shared catalogs
    RINGS,
    SHAPES,
    assert_stores_message_identical,
    star_catalog,
)


def _engines(cat, ring, use_plans=True):
    """(per-edge reference, level-fused) engine pair on separate stores."""
    jt = jt_from_catalog(cat)
    ref = CJTEngine(jt, cat, ring, store=MessageStore(), use_plans=False)
    fus = CJTEngine(
        jt, cat, ring, store=MessageStore(), use_plans=use_plans,
        batch_calibration=True, fuse_level_kernel=True,
    )
    return jt, ref, fus


# ---------------------------------------------------------------------------
# metamorphic parity: level-fused ≡ per-edge, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", sorted(RINGS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_level_fused_equals_per_edge(ring_name, shape):
    cat = SHAPES[shape](seed=3)
    measure = None if ring_name == "count" else ("F", "m")
    gamma = ("c",) if shape != "star" else ("c", "d")
    q = Query.make(cat, ring=ring_name, measure=measure, group_by=gamma)
    jt, ref, fus = _engines(cat, RINGS[ring_name])
    ref.calibrate(q, batch=False)
    fus.calibrate(q, batch=True)
    assert ref.is_calibrated(q) and fus.is_calibrated(q)
    assert_stores_message_identical(ref, fus, q)
    # one host dispatch per level, never more
    levels = max(len(jt.calibration_levels(b)) for b in jt.bags)
    assert 0 < fus.plans.stats.calibration_dispatches <= levels


@pytest.mark.parametrize("use_plans", [False, True])
def test_level_fused_plans_on_off(use_plans):
    """Plans off: the fuse flag is inert (no plan cache to fuse through) and
    the per-edge loop runs — results stay bit-identical either way."""
    cat = star_catalog(seed=5)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    _, ref, fus = _engines(cat, sr.SUM, use_plans=use_plans)
    ref.calibrate(q, batch=False)
    fus.calibrate(q)
    assert_stores_message_identical(ref, fus, q)
    if not use_plans:
        assert fus.plans is None


def test_fused_launch_counters():
    """A fused offline pass records ≥ 1 multi-segment launch covering > 1
    message, and the launch count never exceeds the dispatch count."""
    cat = star_catalog(seed=7)
    _, _, fus = _engines(cat, sr.SUM)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    fus.calibrate(q, batch=True)
    st = fus.plans.stats
    assert st.fused_level_launches >= 1, st
    assert st.fused_level_messages > st.fused_level_launches, (
        "a fused launch should cover several same-level messages"
    )
    assert st.fused_level_launches <= st.calibration_dispatches


def test_fused_vs_unfused_batched_identical():
    """Fused levels vs the (PR 5) batched-but-unfused path: same bits, and
    fusion never dispatches more often."""
    cat = star_catalog(seed=11)
    jt = jt_from_catalog(cat)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c", "d"))
    unf = CJTEngine(jt, cat, sr.SUM, store=MessageStore(),
                    batch_calibration=True, fuse_level_kernel=False)
    fus = CJTEngine(jt, cat, sr.SUM, store=MessageStore(),
                    batch_calibration=True, fuse_level_kernel=True)
    unf.calibrate(q, batch=True)
    fus.calibrate(q, batch=True)
    assert_stores_message_identical(unf, fus, q)
    assert fus.plans.stats.fused_level_launches > 0
    assert unf.plans.stats.fused_level_launches == 0
    assert (fus.plans.stats.calibration_dispatches
            <= unf.plans.stats.calibration_dispatches)


# ---------------------------------------------------------------------------
# MOMENTS through the kernel: stacked-leaf ≡ lax fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", ["moments", "sum"])
def test_kernel_vs_lax_fallback_parity(ring_name, monkeypatch):
    """Forcing the cost gate shut (REPRO_PLAN_KERNEL_COST=0) must not change
    a single bit — the stacked-leaf kernel and the lax segment path are
    ⊕-order-identical on exactly-representable data."""
    cat = star_catalog(seed=13)
    jt = jt_from_catalog(cat)
    q = Query.make(cat, ring=ring_name, measure=("F", "m"), group_by=("c",))
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", str(1 << 30))
    ker = CJTEngine(jt, cat, RINGS[ring_name], store=MessageStore(),
                    batch_calibration=True, fuse_level_kernel=True)
    ker.calibrate(q, batch=True)
    assert ker.plans.stats.kernel_execs > 0, "kernel path not exercised"
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", "0")
    lax = CJTEngine(jt, cat, RINGS[ring_name], store=MessageStore(),
                    batch_calibration=True, fuse_level_kernel=True)
    lax.calibrate(q, batch=True)
    assert lax.plans.stats.kernel_execs == 0
    assert lax.plans.stats.fallback_execs > 0
    assert_stores_message_identical(ker, lax, q)


def test_moments_rides_segment_kernel():
    """MOMENTS (compound (c, s, q) element) is kernel-eligible: its three
    equal-shape leaves stack as f32 columns through one segment launch."""
    cat = star_catalog(seed=17)
    _, _, fus = _engines(cat, sr.MOMENTS)
    q = Query.make(cat, ring="moments", measure=("F", "m"), group_by=("c",))
    fus.calibrate(q, batch=True)
    assert fus.plans.stats.kernel_execs > 0


# ---------------------------------------------------------------------------
# env gate + cost-profile resolution chain
# ---------------------------------------------------------------------------

def test_env_gate_fuse_level_kernel(monkeypatch):
    cat = star_catalog(seed=19)
    monkeypatch.setenv("REPRO_FUSE_LEVEL_KERNEL", "0")
    t = Treant(cat, ring=sr.SUM)
    assert not t.fuse_level_kernel and not t.engine.fuse_level_kernel
    monkeypatch.setenv("REPRO_FUSE_LEVEL_KERNEL", "1")
    t = Treant(cat, ring=sr.SUM)
    assert t.fuse_level_kernel and t.engine.fuse_level_kernel
    # explicit argument wins over the env
    t = Treant(cat, ring=sr.SUM, fuse_level_kernel=False)
    assert not t.engine.fuse_level_kernel
    # sibling engines inherit the flag
    assert t.engine_for("count", ("F", "m")).fuse_level_kernel is False


def test_kernel_cost_profile_resolution(monkeypatch, tmp_path):
    prof = tmp_path / "kernel_costs.json"
    prof.write_text(json.dumps(
        {"derived": {"plan_kernel_cost": 123456,
                     "calibration_union_budget": 777}}))
    monkeypatch.setenv(kernel_costs.PROFILE_ENV, str(prof))
    monkeypatch.delenv("REPRO_PLAN_KERNEL_COST", raising=False)
    monkeypatch.delenv("REPRO_CALIBRATION_UNION_BUDGET", raising=False)
    kernel_costs.reset_cache()
    try:
        assert kernel_costs.derived_plan_kernel_cost() == 123456
        assert kernel_costs.derived_union_budget() == 777
        # the plan gates default to the measured values ...
        assert plans_mod._kernel_cost_max() == 123456
        assert plans_mod.calibration_union_budget() == 777
        # ... but explicit env overrides always win
        monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", "42")
        monkeypatch.setenv("REPRO_CALIBRATION_UNION_BUDGET", "64")
        assert plans_mod._kernel_cost_max() == 42
        assert plans_mod.calibration_union_budget() == 64
    finally:
        kernel_costs.reset_cache()


def test_kernel_cost_profile_disabled_and_malformed(monkeypatch, tmp_path):
    # "" disables the profile → historical static defaults
    monkeypatch.setenv(kernel_costs.PROFILE_ENV, "")
    monkeypatch.delenv("REPRO_PLAN_KERNEL_COST", raising=False)
    monkeypatch.delenv("REPRO_CALIBRATION_UNION_BUDGET", raising=False)
    kernel_costs.reset_cache()
    try:
        assert kernel_costs.load_profile() is None
        assert plans_mod._kernel_cost_max() == 1 << 19
        assert plans_mod.calibration_union_budget() == 512
        # malformed JSON / non-positive values degrade to None, not a crash
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        monkeypatch.setenv(kernel_costs.PROFILE_ENV, str(bad))
        kernel_costs.reset_cache()
        assert kernel_costs.load_profile() is None
        neg = tmp_path / "neg.json"
        neg.write_text(json.dumps({"derived": {"plan_kernel_cost": -5}}))
        monkeypatch.setenv(kernel_costs.PROFILE_ENV, str(neg))
        kernel_costs.reset_cache()
        assert kernel_costs.derived_plan_kernel_cost() is None
    finally:
        kernel_costs.reset_cache()


# ---------------------------------------------------------------------------
# cache_stats aggregation (satellite 6 regression)
# ---------------------------------------------------------------------------

def test_cache_stats_max_fields_aggregation():
    """Multi-ring dashboards aggregate plan counters across sibling engines:
    width counters (PlanStats.MAX_FIELDS) take the max, everything else
    sums.  The old hardcoded tuple silently summed newly added width fields;
    the aggregation must now be driven by the declaration."""
    assert set(PlanStats.MAX_FIELDS) <= set(PlanStats().as_dict())
    cat = star_catalog(seed=23)
    t = Treant(cat, ring=sr.SUM, use_plans=True, batch_calibration=True,
               fuse_level_kernel=True)
    for ring_name, measure in [("sum", ("F", "m")), ("moments", ("F", "m")),
                               ("tropical_min", ("F", "m"))]:
        q = Query.make(cat, ring=ring_name, measure=measure, group_by=("c",))
        t.engine_for(ring_name, measure).calibrate(q, batch=True)
    engines = list(t._engines.values())
    assert len(engines) >= 3
    agg = t.cache_stats()["plans"]
    for field in PlanStats.MAX_FIELDS:
        assert agg[field] == max(e.plans.stats.as_dict()[field]
                                 for e in engines), field
    for field in ("calibration_dispatches", "fused_level_launches",
                  "fused_level_messages", "plans_built"):
        assert agg[field] == sum(e.plans.stats.as_dict()[field]
                                 for e in engines), field
    assert agg["fused_level_launches"] > 0


def test_fused_counters_survive_jit_cache_hits():
    """A second calibration of an identical-structure query hits the traced
    level plan (plan_hits) yet still counts its fused launches."""
    cat = star_catalog(seed=29)
    jt = jt_from_catalog(cat)
    eng = CJTEngine(jt, cat, sr.SUM, store=MessageStore(),
                    batch_calibration=True, fuse_level_kernel=True)
    q1 = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    q2 = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("d",))
    eng.calibrate(q1, batch=True)
    first = eng.plans.stats.fused_level_launches
    assert first > 0
    eng.calibrate(q2, batch=True)
    assert eng.plans.stats.fused_level_launches >= first


# ---------------------------------------------------------------------------
# row blocks: bodies over a slab larger than the budget scan equal row blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", sorted(RINGS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_row_blocked_plans_equal_per_edge(ring_name, shape, monkeypatch):
    """With a row-slab budget of one byte every sparse body (single, batched
    fan-out and level) scans the smallest allowed row blocks and ⊕-combines
    their partial factors; on integer data the store and a σ'd read stay
    bit-identical to the per-edge reference."""
    monkeypatch.setattr(plans_mod, "ROW_SLAB_BYTES", 1)
    monkeypatch.setattr(plans_mod, "_MIN_BLOCK_ROWS", 16)
    cat = SHAPES[shape](seed=3)
    measure = None if ring_name == "count" else ("F", "m")
    gamma = ("c",) if shape != "star" else ("c", "d")
    q = Query.make(cat, ring=ring_name, measure=measure, group_by=gamma)
    jt, ref, blk = _engines(cat, RINGS[ring_name])
    ref.calibrate(q, batch=False)
    blk.calibrate(q, batch=True)
    assert_stores_message_identical(ref, blk, q)
    assert plans_mod._row_blocks(1024, 1) == 1024 // 16
    dom_a = cat.get("F").domains["a"]
    qs = [q.with_predicate(mask_in(dom_a, vals, attr="a"))
          for vals in ([0, 2], [1, 3, 4])]
    want = [ref.execute(x)[0] for x in qs]
    got = blk.execute_many(qs)
    for w, (g, _) in zip(want, got):
        for a, b in zip(jax.tree_util.tree_leaves(w.field),
                        jax.tree_util.tree_leaves(g.field)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
