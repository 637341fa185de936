"""Sharded CJT execution (ISSUE 9): metamorphic sharded ≡ single-device.

The tentpole's correctness spine: row-sharding the fact relation over a
device mesh and ⊕-all-reducing the γ-indexed partials must be **invisible**
— on integer data the sharded engine's answers and stored messages are
bit-identical to a single-device engine across rings (SUM/COUNT/MIN/MAX;
MOMENTS under allclose), join shapes (chain/star/bushy), plans on/off and
mesh widths 1/2/8.  Sharding is an execution strategy, never a semantic:
rings without a collective (BOOL) run unsharded, and deltas
(``apply_delta`` / ``stream().flush()``) shard the same way the base scan
does.

Mesh-dependent tests skip unless the process has enough virtual devices —
run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
sharded leg does; see also ``REPRO_SHARD_DEVICES``).
"""

import jax
import numpy as np
import pytest

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import CJTEngine, MessageStore, Query, Treant, jt_from_catalog
from repro.core import distributed as dist
from repro.core import semiring as sr
from repro.relational.relation import Catalog, mask_in

from test_level_calibration import (
    RINGS,
    SHAPES,
    assert_stores_message_identical,
    bushy_catalog,
    chain_catalog,
)


def mesh_or_skip(nshards: int):
    if nshards > 1 and jax.device_count() < nshards:
        pytest.skip(
            f"needs {nshards} devices (XLA_FLAGS="
            f"--xla_force_host_platform_device_count={nshards})"
        )
    return dist.make_engine_mesh(nshards)


def _query(cat, ring_name, shape="chain"):
    measure = None if ring_name in ("count", "bool") else ("F", "m")
    gamma = ("c",) if shape != "star" else ("c", "d")
    dom_a = cat.get("F").domains["a"]
    return Query.make(
        cat, ring=ring_name, measure=measure, group_by=gamma,
        predicates=(mask_in(dom_a, [1, 2, 3], attr="a"),),
    )


def _engines(shape, ring_name, nshards, seed=3, use_plans=True):
    """(sharded, reference) engine pair over identically-seeded catalogs.

    Separate Catalog instances keep the reference engine free of the
    sharded catalog's row placement — same seed, same bits."""
    mesh = mesh_or_skip(nshards)
    ring = RINGS[ring_name] if ring_name in RINGS else sr.get(ring_name)
    cats = [SHAPES[shape](seed=seed) for _ in range(2)]
    if mesh is not None:
        cats[0].set_row_placement(dist.row_placement(mesh))
    shd = CJTEngine(jt_from_catalog(cats[0]), cats[0], ring,
                    store=MessageStore(), use_plans=use_plans, mesh=mesh)
    ref = CJTEngine(jt_from_catalog(cats[1]), cats[1], ring,
                    store=MessageStore(), use_plans=use_plans)
    return shd, ref, cats


def _assert_factors_match(got, want, exact=True):
    l1 = jax.tree_util.tree_leaves(got.field)
    l2 = jax.tree_util.tree_leaves(want.field)
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        a, b = np.asarray(a), np.asarray(b)
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6)


# ---------------------------------------------------------------------------
# metamorphic parity: sharded ≡ single-device, bit-identical on integer data
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", sorted(RINGS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_sharded_matches_single_device(ring_name, shape):
    shd, ref, cats = _engines(shape, ring_name, nshards=8)
    q1, q2 = _query(cats[0], ring_name, shape), _query(cats[1], ring_name, shape)
    exact = ring_name != "moments"
    # cold execute, batched calibration, warm re-execute: every path agrees
    _assert_factors_match(shd.execute(q1)[0], ref.execute(q2)[0], exact)
    shd.calibrate(q1, batch=True)
    ref.calibrate(q2, batch=True)
    _assert_factors_match(shd.execute(q1)[0], ref.execute(q2)[0], exact)
    if exact:
        assert_stores_message_identical(shd, ref, q1)
    if ring_name in ("sum", "count", "tropical_min", "tropical_max"):
        assert shd.plans.stats.shard_execs > 0
        assert shd.plans.stats.allreduce_bytes > 0
        assert ref.plans.stats.shard_execs == 0


@pytest.mark.parametrize("nshards", [1, 2, 8])
@pytest.mark.parametrize("use_plans", [True, False])
def test_sharded_mesh_widths_and_plans_on_off(nshards, use_plans):
    """Every mesh width gives the single-device bits; with plans off the
    mesh is inert (sharding lives in the plan cache) but must stay correct."""
    shd, ref, cats = _engines("chain", "sum", nshards, use_plans=use_plans)
    q1, q2 = _query(cats[0], "sum"), _query(cats[1], "sum")
    _assert_factors_match(shd.execute(q1)[0], ref.execute(q2)[0])
    shd.calibrate(q1, batch=True)
    ref.calibrate(q2, batch=True)
    _assert_factors_match(shd.execute(q1)[0], ref.execute(q2)[0])
    assert_stores_message_identical(shd, ref, q1)


def test_sharded_update_then_read():
    """apply_delta on a sharded fact: maintained messages equal the
    single-device maintenance AND a cold rebuild over the updated catalog."""
    shd, ref, cats = _engines("chain", "sum", nshards=8, seed=7)
    q1, q2 = _query(cats[0], "sum"), _query(cats[1], "sum")
    shd.calibrate(q1, batch=True)
    ref.calibrate(q2, batch=True)
    rng = np.random.default_rng(5)
    n = 96
    codes = {a: rng.integers(0, cats[0].get("F").domains[a], n) for a in ("a", "b")}
    meas = {"m": rng.integers(0, 16, n).astype(np.float32)}
    for eng, cat in ((shd, cats[0]), (ref, cats[1])):
        rel, delta = cat.get("F").append_rows(
            {a: v.copy() for a, v in codes.items()}, measures={"m": meas["m"].copy()}
        )
        cat.put(rel)
        if eng is shd:
            q1, st = eng.apply_delta(q1, delta)
        else:
            q2, st = eng.apply_delta(q2, delta)
        assert not st.fallback
    got, es = shd.execute(q1)
    assert es.messages_computed == 0  # maintenance kept the CJT warm
    _assert_factors_match(got, ref.execute(q2)[0])
    cold = CJTEngine(jt_from_catalog(cats[1]), cats[1], sr.SUM,
                     store=MessageStore(), use_plans=False)
    _assert_factors_match(got, cold.execute(q2)[0])


def test_sharded_stream_flush_parity():
    """stream().flush() on a sharded Treant coalesces + maintains the same
    bits as an unsharded Treant fed the identical micro-batches."""
    mesh = mesh_or_skip(8)
    pair = []
    for m in (mesh, 0):  # mesh=0 opts out even when REPRO_SHARD_DEVICES is set
        cat = chain_catalog(seed=9)
        t = Treant(cat, ring=sr.SUM, mesh=m)
        q = _query(cat, "sum")
        t.engine.calibrate(q, batch=True)
        rng = np.random.default_rng(21)
        buf = t.stream("F")
        for _ in range(3):
            n = 40
            buf.append(
                {a: rng.integers(0, cat.get("F").domains[a], n) for a in ("a", "b")},
                measures={"m": rng.integers(0, 16, n).astype(np.float32)},
            )
        mask = np.zeros(cat.get("F").num_rows + buf.pending_appends, bool)
        mask[rng.choice(cat.get("F").num_rows, 25, replace=False)] = True
        buf.delete(mask)
        res = t.flush()
        assert res.relations == ["F"]
        q = q.with_version("F", cat.latest_version("F"))
        pair.append(t.engine.execute(q)[0])
    _assert_factors_match(pair[0], pair[1])


def test_sharded_mid_level_abandonment():
    """Mirror of test_abandoned_iterator_keeps_completed_levels on a mesh:
    abandoning the level iterator mid-pass keeps every completed level's
    messages servable, and the finished pass matches single-device bits."""
    mesh = mesh_or_skip(8)
    cat = bushy_catalog(seed=11)
    cat.set_row_placement(dist.row_placement(mesh))
    jt = jt_from_catalog(cat)
    eng = CJTEngine(jt, cat, sr.SUM, store=MessageStore(), mesh=mesh)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    it = eng.calibrate_levels_iter(q)
    completed = [next(it), next(it)]  # abandon mid-pass
    del it
    placement = eng.place_predicates(q)
    for level in completed:
        for (u, v) in level:
            base = eng.edge_sig(q, u, v, placement)
            assert eng.store.contains(base, eng.gamma_carry(q, u, v)), (
                f"completed-level message {(u, v)} not servable"
            )
    stats = eng.calibrate(q, batch=True)
    assert eng.is_calibrated(q)
    assert stats.messages_reused >= sum(len(lv) for lv in completed)
    ref_cat = bushy_catalog(seed=11)
    ref = CJTEngine(jt_from_catalog(ref_cat), ref_cat, sr.SUM,
                    store=MessageStore())
    ref.calibrate(
        Query.make(ref_cat, ring="sum", measure=("F", "m"), group_by=("c",)),
        batch=True,
    )
    assert_stores_message_identical(eng, ref, q)


def test_bool_ring_falls_back_unsharded():
    """BOOL has no ⊕-inverse and no min/max collective: the plan cache must
    refuse to shard (correct answers, zero sharded dispatches)."""
    shd, ref, cats = _engines("chain", "bool", nshards=8)
    q1, q2 = _query(cats[0], "bool"), _query(cats[1], "bool")
    _assert_factors_match(shd.execute(q1)[0], ref.execute(q2)[0])
    assert shd.plans.stats.shard_execs == 0
    assert shd.plans.stats.allreduce_bytes == 0


def test_shard_counters_surface_in_cache_stats():
    mesh = mesh_or_skip(8)
    cat = chain_catalog(seed=3)
    t = Treant(cat, ring=sr.SUM, use_plans=True, mesh=mesh)
    t.engine.execute(_query(cat, "sum"))
    st = t.cache_stats()["plans"]
    assert st["shard_execs"] > 0
    assert st["allreduce_bytes"] > 0
    assert st["shard_imbalance"] >= 1.0


# ---------------------------------------------------------------------------
# device-free units: collective map, imbalance math, mesh acquisition
# ---------------------------------------------------------------------------

def test_ring_collective_map():
    assert dist.ring_collective(sr.SUM) is jax.lax.psum
    assert dist.ring_collective(sr.COUNT) is jax.lax.psum
    assert dist.ring_collective(sr.MOMENTS) is jax.lax.psum
    assert dist.ring_collective(sr.TROPICAL_MIN) is jax.lax.pmin
    assert dist.ring_collective(sr.TROPICAL_MAX) is jax.lax.pmax
    assert dist.ring_collective(sr.BOOL) is None


def test_shard_imbalance_math():
    # perfectly balanced: 512 rows over 8 shards of a 512 bucket
    assert dist.shard_imbalance(512, 512, 8) == pytest.approx(1.0)
    # 500 rows padded to 512: the fullest shard holds 64/62.5 of its share
    assert dist.shard_imbalance(500, 512, 8) == pytest.approx(512 / 500)
    # tiny relation, one shard does all the work
    assert dist.shard_imbalance(3, 64, 8) == pytest.approx(8.0)
    assert dist.shard_imbalance(100, 128, 1) == 1.0
    assert dist.shard_imbalance(0, 64, 8) == 0.0


def test_make_engine_mesh_disabled(monkeypatch):
    assert dist.make_engine_mesh(0) is None
    assert dist.make_engine_mesh(1) is None
    monkeypatch.delenv("REPRO_SHARD_DEVICES", raising=False)
    assert dist.shard_devices() == 0
    assert dist.make_engine_mesh() is None
    monkeypatch.setenv("REPRO_SHARD_DEVICES", "not-a-number")
    assert dist.shard_devices() == 0
    monkeypatch.setenv("REPRO_SHARD_DEVICES", "8")
    assert dist.shard_devices() == 8
    # more shards than devices: an error, never a silently smaller mesh
    monkeypatch.setenv("REPRO_SHARD_DEVICES", str(jax.device_count() * 1000))
    with pytest.raises(ValueError, match="devices"):
        dist.make_engine_mesh()
