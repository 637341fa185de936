"""Compiled message plans (core.plans): parity, metamorphic and cache tests.

- Parity: the compiled/Pallas path must match the legacy un-jitted reference
  path across non-tile-divisible N/G, min/max segment ops, trailing statistic
  dims (MOMENTS) and predicate masks.
- Metamorphic: with integer-valued measures (exactly representable in f32,
  so every summation order yields the same bits) ``execute`` must be
  **bit-identical** with the plan cache on vs off.
- Caching: structural reuse across versions/masks, bounded signature memo,
  Σ-widening probe stats.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import CJTEngine, MessageStore, Query, Treant, jt_from_catalog
from repro.core import semiring as sr
from repro.core.factor import Factor
from repro.relational.relation import LRU, Catalog, Relation, mask_in

N_FACT = 600  # > one 512-row kernel tile → exercises row padding


def star_catalog(n_fact: int = N_FACT, seed: int = 0) -> Catalog:
    """Tiny star: F(a,b)+m ← S(b,c), T(a,d).  Domains straddle the 8-lane
    tile minimum (5 < 8 ≤ 13) so the kernel's group padding is exercised;
    measures are small integers so f32 sums are exact (bitwise-stable)."""
    rng = np.random.default_rng(seed)
    doms = {"a": 13, "b": 7, "c": 10, "d": 5}

    def codes(attrs, n):
        return {x: rng.integers(0, doms[x], n).astype(np.int32) for x in attrs}

    f = Relation("F", ("a", "b"), codes(("a", "b"), n_fact), doms,
                 measures={"m": rng.integers(0, 16, n_fact).astype(np.float32)})
    s = Relation("S", ("b", "c"), codes(("b", "c"), 77), doms,
                 measures={"w": rng.integers(0, 8, 77).astype(np.float32)})
    t = Relation("T", ("a", "d"), codes(("a", "d"), 29), doms)
    return Catalog([f, s, t])


def engines(cat, ring, **kw):
    jt = jt_from_catalog(cat)
    ref = CJTEngine(jt, cat, ring, use_plans=False, **kw)
    pln = CJTEngine(jt, cat, ring, use_plans=True, **kw)
    return ref, pln


def assert_factors_equal(f1: Factor, f2: Factor, exact: bool):
    assert f1.attrs == f2.attrs
    l1 = jax.tree_util.tree_leaves(f1.field)
    l2 = jax.tree_util.tree_leaves(f2.field)
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        a, b = np.asarray(a), np.asarray(b)
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# parity: compiled path ≡ reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group_by", [(), ("c",), ("c", "d")])
def test_sparse_parity_sum_nondivisible(group_by):
    cat = star_catalog()
    ref, pln = engines(cat, sr.SUM)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=group_by)
    f1, _ = ref.execute(q)
    f2, s2 = pln.execute(q)
    assert_factors_equal(f1, f2, exact=True)
    assert s2.plan_traces > 0 and s2.kernel_execs > 0


@pytest.mark.parametrize("ring,name", [(sr.TROPICAL_MIN, "tropical_min"),
                                       (sr.TROPICAL_MAX, "tropical_max")])
def test_sparse_parity_minmax_kernel_ops(ring, name):
    cat = star_catalog(seed=3)
    ref, pln = engines(cat, ring)
    q = Query.make(cat, ring=name, measure=("F", "m"), group_by=("c",))
    f1, _ = ref.execute(q)
    f2, s2 = pln.execute(q)
    # min/max are order-insensitive: exact equality regardless of tiling
    assert_factors_equal(f1, f2, exact=True)
    assert s2.kernel_execs > 0


def test_sparse_parity_moments_trailing_dims():
    """MOMENTS (compound (c,s,q) element) rides the segment kernel as three
    stacked f32 columns — one segment pass for count/sum/sumsq — and must
    flow through the compiled plan with its tuple field intact."""
    cat = star_catalog(seed=5)
    ref, pln = engines(cat, sr.MOMENTS)
    q = Query.make(cat, ring="moments", measure=("F", "m"), group_by=("c",))
    f1, _ = ref.execute(q)
    f2, s2 = pln.execute(q)
    assert len(jax.tree_util.tree_leaves(f2.field)) == 3
    assert_factors_equal(f1, f2, exact=True)
    assert s2.plan_traces > 0 and s2.kernel_execs > 0  # stacked-leaf kernel


def test_sparse_parity_predicate_masks():
    cat = star_catalog(seed=7)
    ref, pln = engines(cat, sr.SUM)
    base = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("b",))
    q = base.with_predicate(mask_in(10, [1, 3, 9], attr="c"))
    q = q.with_predicate(mask_in(5, [0, 2], attr="d"))
    f1, _ = ref.execute(q)
    f2, _ = pln.execute(q)
    assert_factors_equal(f1, f2, exact=True)


def test_dense_two_factor_semiring_contract_route():
    """With everything densified, bag contraction takes the dense plan; the
    2-factor arithmetic case must route through the semiring_contract kernel
    and agree with the legacy einsum path bit-for-bit on integer data."""
    cat = star_catalog(seed=11)
    ref, pln = engines(cat, sr.SUM, dense_rows_threshold=10**9)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    f1, _ = ref.execute(q)
    f2, s2 = pln.execute(q)
    assert_factors_equal(f1, f2, exact=True)
    assert s2.kernel_execs > 0


@pytest.mark.parametrize("ring,name", [(sr.TROPICAL_MIN, "tropical_min"),
                                       (sr.TROPICAL_MAX, "tropical_max")])
def test_dense_two_factor_tropical_contract_route(ring, name):
    """The dense 2-factor tropical case (⊗ = +, ⊕ = min/max is exactly the
    tropical matmul) routes through the tropical_contract kernel under the
    same measured cost gate, bit-identical to the legacy reduce path."""
    cat = star_catalog(seed=11)
    ref, pln = engines(cat, ring, dense_rows_threshold=10**9)
    q = Query.make(cat, ring=name, measure=("F", "m"), group_by=("c",))
    f1, _ = ref.execute(q)
    f2, s2 = pln.execute(q)
    assert_factors_equal(f1, f2, exact=True)
    assert s2.kernel_execs > 0, "tropical dense route must hit the kernel"


# ---------------------------------------------------------------------------
# metamorphic: plan cache on ≡ off, bit-identical
# ---------------------------------------------------------------------------

def test_metamorphic_execute_bit_identical_plans_on_vs_off():
    cat = star_catalog(seed=13)
    queries = []
    for ring_name, measure in [("count", None), ("sum", ("F", "m")),
                               ("moments", ("F", "m"))]:
        q0 = Query.make(cat, ring=ring_name, measure=measure, group_by=("c",))
        queries += [
            q0,
            q0.with_group_by("c", "d"),
            q0.with_predicate(mask_in(7, [0, 2, 5], attr="b")),
            q0.with_removed("T"),
        ]
    ring_of = {"count": sr.COUNT, "sum": sr.SUM, "moments": sr.MOMENTS}
    for q in queries:
        ref, pln = engines(cat, ring_of[q.ring_name])
        f1, _ = ref.execute(q)
        f2, _ = pln.execute(q)
        assert_factors_equal(f1, f2, exact=True)


# ---------------------------------------------------------------------------
# structural plan reuse
# ---------------------------------------------------------------------------

def test_version_bump_reuses_compiled_plan():
    """A measure perturbation bumps every Prop-2 signature but keeps the
    structure: the second execution must add zero new plan traces."""
    cat = star_catalog(seed=17)
    jt = jt_from_catalog(cat)
    eng = CJTEngine(jt, cat, sr.SUM)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    eng.execute(q)
    built = eng.plans.stats.plans_built
    cat.put(cat.get("F").perturb_measure("m", 0.5, seed=1, version="v1"))
    q1 = q.with_version("F", "v1")
    _, s1 = eng.execute(q1)
    assert eng.plans.stats.plans_built == built
    assert s1.plan_traces == 0 and s1.plan_hits > 0


def test_new_predicate_mask_reuses_compiled_plan():
    cat = star_catalog(seed=19)
    jt = jt_from_catalog(cat)
    eng = CJTEngine(jt, cat, sr.SUM)
    q0 = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    eng.execute(q0.with_predicate(mask_in(5, [0, 1], attr="d")))
    built = eng.plans.stats.plans_built
    _, s = eng.execute(q0.with_predicate(mask_in(5, [2, 4], attr="d")))
    assert eng.plans.stats.plans_built == built  # same structure, new σ mask
    assert s.plan_traces == 0


def test_delta_maintenance_runs_through_plans():
    # explicit use_plans=True: the REPRO_USE_PLANS=0 CI leg must not turn
    # this into a plans-off engine (the assertions below count kernel execs)
    cat = star_catalog(seed=23)
    tre = Treant(cat, ring=sr.SUM, use_plans=True)
    q = Query.make(cat, ring="sum", measure=("F", "m"), group_by=("c",))
    tre.register_dashboard("viz", q)
    rng = np.random.default_rng(29)
    f = cat.get("F")
    new_rel, delta = f.append_rows(
        {a: rng.integers(0, f.domains[a], 8).astype(np.int32) for a in f.attrs},
        {"m": rng.integers(0, 16, 8).astype(np.float32)},
    )
    res = tre.update(new_rel, delta)
    assert res.queries_maintained == 1 and res.queries_fallback == 0
    got = tre.read("u", "viz").factor
    # oracle: rebuild from scratch on the merged relation, legacy path
    cat2 = Catalog([new_rel, cat.get("S"), cat.get("T")])
    ref = CJTEngine(jt_from_catalog(cat2), cat2, sr.SUM, use_plans=False)
    want, _ = ref.execute(Query.make(cat2, ring="sum", measure=("F", "m"),
                                     group_by=("c",)))
    assert_factors_equal(want, got, exact=True)
    assert tre.cache_stats()["plans"]["kernel_execs"] > 0


# ---------------------------------------------------------------------------
# bounded caches + Σ-widening probe index
# ---------------------------------------------------------------------------

def test_sig_memo_is_bounded():
    cat = star_catalog(seed=31)
    jt = jt_from_catalog(cat)
    eng = CJTEngine(jt, cat, sr.COUNT)
    eng._sig_memo = LRU(capacity=16)
    q0 = Query.make(cat, ring="count")
    for lo in range(8):  # 8 distinct interaction queries
        eng.execute(q0.with_predicate(mask_in(10, [lo], attr="c")))
    assert len(eng._sig_memo) <= 16


def test_widen_probe_short_circuit_and_stats():
    store = MessageStore()
    wide = Factor(("a", "b"), jnp.arange(12, dtype=jnp.float32).reshape(4, 3), sr.SUM)
    store.put("base", ("a", "b"), wide)
    # γ outside the widen union: no scan at all
    assert store.get("base", ("z",)) is None
    assert store.widen_scans == 0 and store.widen_scan_steps == 0
    # γ subset: scanned, narrowed, counted
    got = store.get("base", ("a",))
    assert got is not None and got.attrs == ("a",)
    np.testing.assert_allclose(np.asarray(got.field),
                               np.asarray(wide.field).sum(axis=1))
    assert store.widen_hits == 1
    assert store.widen_scans == 1 and store.widen_scan_steps >= 1
    # narrowing stored the result: the repeat probe is an exact hit, no scan
    scans = store.widen_scans
    assert store.get("base", ("a",)) is not None
    assert store.widen_scans == scans


def test_widen_probe_prefers_smallest_superset():
    store = MessageStore()
    big = Factor(("a", "b", "c"),
                 jnp.ones((4, 3, 2), jnp.float32), sr.SUM)
    small = Factor(("a", "b"), jnp.full((4, 3), 2.0, jnp.float32), sr.SUM)
    store.put("base", ("a", "b", "c"), big)
    store.put("base", ("a", "b"), small)
    got = store.get("base", ("a",))
    # smallest superset (a,b) narrows first: sum over b of the 2.0 factor
    np.testing.assert_allclose(np.asarray(got.field), np.full((4,), 6.0))


def test_widen_index_dropped_on_eviction():
    """Evicting a message must also drop its Σ-widening index entries —
    otherwise a long update stream grows the probe index without bound."""
    f = Factor(("a",), jnp.ones((64,), jnp.float32), sr.SUM)
    store = MessageStore(max_bytes=2 * 64 * 4)  # room for 2 factors
    for i in range(8):
        store.put(f"base{i}", ("a",), f)
    assert len(store) == 2
    assert len(store._widen) == 2
    assert len(store._sig_index) == 2
    assert sum(len(v) for v in store._widen_bysize.values()) == 2
    # evicted entries no longer advertise as contained
    assert not store.contains("base0", ("a",))
    assert store.contains("base7", ("a",))


def test_store_snapshot_restore_keeps_widen_index():
    store = MessageStore()
    store.put("base", ("a", "b"),
              Factor(("a", "b"), jnp.ones((2, 2), jnp.float32), sr.SUM))
    snap = store.snapshot()
    store.put("other", ("c",), Factor(("c",), jnp.ones((2,), jnp.float32), sr.SUM))
    store.restore(snap)
    assert store.get("base", ("a",)) is not None  # widen index rebuilt
    assert store.get("other", ("c",)) is None


def test_catalog_dev_codes_cached_and_lru_bounded():
    cat = star_catalog(seed=37)
    rel = cat.get("F")
    idx1, total1 = cat.dev_flat_codes(rel, ("a", "b"))
    idx2, total2 = cat.dev_flat_codes(rel, ("a", "b"))
    assert idx1 is idx2 and total1 == total2 == 13 * 7
    want = np.ravel_multi_index(
        (rel.codes["a"].astype(np.int64), rel.codes["b"].astype(np.int64)), (13, 7)
    )
    # codes are padded to the plan row bucket: real rows exact, pad rows 0
    assert idx1.shape == (rel.row_bucket,) and rel.row_bucket >= rel.num_rows
    np.testing.assert_array_equal(np.asarray(idx1)[: rel.num_rows], want)
    np.testing.assert_array_equal(np.asarray(idx1)[rel.num_rows:], 0)
    cat._dev_codes = LRU(capacity=2)
    for attrs in [("a",), ("b",), ("a", "b")]:
        cat.dev_flat_codes(rel, attrs)
    assert len(cat._dev_codes) <= 2


# ---------------------------------------------------------------------------
# lane-dense rowwise stage ≡ the row-major ``take`` path, plan by plan
# ---------------------------------------------------------------------------

# fact attrs a, b, c; carried γ x, y.  The messages gather at one code (a), a
# flat two-attr code (b, c: 70 entries, over the one-column take limit) and
# c; the plans below are built from these statics directly, so every plan
# kind meets the same contraction.
LANE_RINGS = {"sum": sr.SUM, "count": sr.COUNT, "tropical_min": sr.TROPICAL_MIN,
              "tropical_max": sr.TROPICAL_MAX}
TROPICAL_ZERO = {"tropical_min": np.inf, "tropical_max": -np.inf}
LANE_REL = ("a", "b", "c")
LANE_DOMS = {"a": 13, "b": 7, "c": 10, "x": 6, "y": 4}
LANE_IN = (("a", "x"), ("b", "c"), ("c", "y"))
LANE_OUT = ("x", "a", "y")


def lane_rows(n: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {a: rng.integers(0, LANE_DOMS[a], n).astype(np.int32) for a in LANE_REL}


def lane_member(ring_name: str, codes: dict, dims: dict, sigma: tuple, seed: int):
    """One member's (vals, fields, masks) as host arrays: small integers, so
    f32 ⊗ and ⊕ are exact in any order; tropical messages hold 0̄ (±inf)."""
    rng = np.random.default_rng(seed)
    n = len(codes["a"])
    vals = (np.ones(n, np.float32) if ring_name == "count"
            else rng.integers(0, 8, n).astype(np.float32))
    fields = []
    for m in LANE_IN:
        f = rng.integers(0, 4, [dims[a] for a in m]).astype(np.float32)
        if ring_name in TROPICAL_ZERO:
            f[rng.random(f.shape) < 0.2] = TROPICAL_ZERO[ring_name]
        fields.append(f)
    masks = [rng.random(LANE_DOMS[a]) < 0.6 for a in sigma]
    return vals, fields, masks


def take_reference(ring_name: str, codes: dict, dims: dict, vals, fields, sigma, masks):
    """The contraction in numpy, gathering row-major with ``take`` as the
    plans did before their rowwise stage went lane-major: (x, a, y)."""
    tropical = ring_name in TROPICAL_ZERO
    mul = np.add if tropical else np.multiply
    zero = np.float32(TROPICAL_ZERO.get(ring_name, 0.0))
    out = vals[:, None, None]
    out = mul(out, np.take(fields[0], codes["a"], axis=0)[:, :, None])
    flat = codes["b"] * LANE_DOMS["c"] + codes["c"]
    out = mul(out, np.take(fields[1].reshape(-1), flat)[:, None, None])
    out = mul(out, np.take(fields[2], codes["c"], axis=0)[:, None, :])
    keep = np.ones(len(vals), bool)
    for a, m in zip(sigma, masks):
        keep &= m[codes[a]]
    out = np.where(keep[:, None, None], out, zero)
    res = np.full((LANE_DOMS["a"], dims["x"], dims["y"]), zero, np.float32)
    add = {"tropical_min": np.minimum, "tropical_max": np.maximum}.get(ring_name, np.add)
    add.at(res, codes["a"], out)
    return res.transpose(1, 0, 2)


def lane_args(codes: dict, sigma: tuple):
    """The shared row-major arguments: (in_idx, pred_codes, seg_idx)."""
    flat = codes["b"] * LANE_DOMS["c"] + codes["c"]
    in_idx = tuple(jnp.asarray(c) for c in (codes["a"], flat, codes["c"]))
    pred_codes = tuple(jnp.asarray(codes[a]) for a in sigma)
    return in_idx, pred_codes, jnp.asarray(codes["a"])


def lane_device(vals, fields, masks):
    return (jnp.asarray(vals), tuple(jnp.asarray(f) for f in fields),
            tuple(jnp.asarray(m) for m in masks))


def assert_lane_equal(fact: Factor, want: np.ndarray):
    assert fact.attrs == LANE_OUT
    np.testing.assert_array_equal(np.asarray(fact.field), want)


@pytest.fixture(params=["kernel", "lax"])
def reduce_path(request, monkeypatch):
    """Route the segment reduction to the (interpreted) kernel or the lax
    path, which takes the lane-major slab transposed once."""
    cost = "1099511627776" if request.param == "kernel" else "0"
    monkeypatch.setenv("REPRO_PLAN_KERNEL_COST", cost)
    return request.param


@pytest.mark.parametrize("sigma", [(), ("b", "c")])
@pytest.mark.parametrize("ring_name", sorted(LANE_RINGS))
def test_lane_dense_scalar_plan_matches_take(ring_name, sigma, reduce_path):
    from repro.core import plans as plans_mod

    n = 1024
    codes = lane_rows(n, seed=1)
    vals, fields, masks = lane_member(ring_name, codes, LANE_DOMS, sigma, seed=2)
    plan = plans_mod._build_sparse_plan(
        LANE_RINGS[ring_name], LANE_REL, LANE_DOMS, LANE_IN, sigma, LANE_OUT, n
    )
    assert plan.uses_kernel == (reduce_path == "kernel")
    in_idx, pred_codes, seg = lane_args(codes, sigma)
    v, f, m = lane_device(vals, fields, masks)
    out = plan.fn(v, f, in_idx, m, pred_codes, seg)
    assert_lane_equal(out, take_reference(ring_name, codes, LANE_DOMS, vals, fields,
                                          sigma, masks))
    # one member: the σ masks are single columns of at most 64 entries and
    # stay on take; the messages go one-hot in every ring, the tropical ones
    # (0̄ = ±inf) with the infinity split
    split = 3 if ring_name in TROPICAL_ZERO else 0
    assert plan.gathers == (3, len(sigma), split)


@pytest.mark.parametrize("ring_name", sorted(LANE_RINGS))
def test_lane_dense_row_blocked_plan_matches_take(ring_name, monkeypatch):
    from repro.core import plans as plans_mod

    monkeypatch.setattr(plans_mod, "ROW_SLAB_BYTES", 1 << 12)
    monkeypatch.setattr(plans_mod, "_MIN_BLOCK_ROWS", 64)
    n, sigma = 2048, ("b",)
    ring = LANE_RINGS[ring_name]
    _, meta = plans_mod._sparse_fn(ring, LANE_REL, LANE_DOMS, LANE_IN, sigma, LANE_OUT, n)
    assert plans_mod._row_blocks(n, meta.row_bytes) > 1
    codes = lane_rows(n, seed=3)
    vals, fields, masks = lane_member(ring_name, codes, LANE_DOMS, sigma, seed=4)
    plan = plans_mod._build_sparse_plan(ring, LANE_REL, LANE_DOMS, LANE_IN, sigma,
                                        LANE_OUT, n)
    in_idx, pred_codes, seg = lane_args(codes, sigma)
    v, f, m = lane_device(vals, fields, masks)
    out = plan.fn(v, f, in_idx, m, pred_codes, seg)
    assert_lane_equal(out, take_reference(ring_name, codes, LANE_DOMS, vals, fields,
                                          sigma, masks))


SHARDED_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, {tests!r})
import test_plans as T
from repro.core import distributed as dist, plans as P

mesh = dist.make_engine_mesh(2)
n, checked = 1024, 0
for ring_name in sorted(T.LANE_RINGS):
    ring = T.LANE_RINGS[ring_name]
    for sigma in ((), ("b", "c")):
        codes = T.lane_rows(n, seed=5)
        in_idx, pred_codes, seg = T.lane_args(codes, sigma)
        one = T.lane_member(ring_name, codes, T.LANE_DOMS, sigma, seed=6)
        plan = P._build_sharded_sparse_plan(ring, T.LANE_REL, T.LANE_DOMS, T.LANE_IN,
                                            sigma, T.LANE_OUT, n, mesh, dist.SHARD_AXIS)
        v, f, m = T.lane_device(*one)
        T.assert_lane_equal(plan.fn(v, f, in_idx, m, pred_codes, seg),
                            T.take_reference(ring_name, codes, T.LANE_DOMS, *one[:2],
                                             sigma, one[2]))
        dims = [dict(T.LANE_DOMS, x=6, y=4), dict(T.LANE_DOMS, x=3, y=2)]
        members = [T.lane_member(ring_name, codes, d, sigma, seed=7 + i)
                   for i, d in enumerate(dims)]
        bplan = P._build_sharded_batched_sparse_plan(
            ring, T.LANE_REL, T.LANE_DOMS, T.LANE_IN, sigma, T.LANE_OUT, n,
            tuple({{"x": d["x"], "y": d["y"]}} for d in dims), mesh, dist.SHARD_AXIS)
        dev = [T.lane_device(*mb) for mb in members]
        outs = bplan.fn(tuple(d[0] for d in dev), tuple(d[1] for d in dev), in_idx,
                        tuple(d[2] for d in dev), pred_codes, seg)
        for out, d, mb in zip(outs, dims, members):
            T.assert_lane_equal(out, T.take_reference(ring_name, codes, d, *mb[:2],
                                                      sigma, mb[2]))
        checked += 1 + len(outs)
print(json.dumps({{"checked": checked}}))
"""


def test_lane_dense_two_shard_plans_match_take():
    """Row-sharded scalar and batched plans over a 2-device mesh: each shard
    runs the lane-major rowwise stage on its row block, the rows axis stays
    minor, and the ⊕-all-reduced factors equal the take reference.  Runs in
    a subprocess with 2 virtual devices, like tests/test_distributed.py."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(here.parent / "src"), str(here)]))
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT.format(tests=str(here))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    checked = json.loads(out.stdout.strip().splitlines()[-1])["checked"]
    assert checked == len(LANE_RINGS) * 2 * 3


# ---------------------------------------------------------------------------
# one-hot gathers of tables that hold infinities ≡ ``take``
# ---------------------------------------------------------------------------

def _gather_table(shape: tuple, infs: str, seed: int) -> np.ndarray:
    """A (D, *cells) f32 table of wide-ranging finite values, ±0.0 and the
    largest finite magnitudes, with -inf and/or +inf in about a fifth of
    its entries each as ``infs`` asks."""
    rng = np.random.default_rng(seed)
    t = (rng.standard_normal(shape) * 10.0 ** rng.integers(-30, 30, shape)).astype(np.float32)
    flat = t.reshape(-1)
    flat[:4] = [-0.0, 0.0, np.finfo(np.float32).max, -np.finfo(np.float32).max]
    for sign in {"neg": (-1,), "pos": (1,), "both": (-1, 1), "none": ()}[infs]:
        t[rng.random(shape) < 0.2] = sign * np.inf
    return t


def _assert_same_bits(got: np.ndarray, want: np.ndarray):
    """Bit for bit, but for the sign of a zero: a -0.0 entry gathered by
    contraction sums exact zeros and comes out +0.0, the same number."""
    assert got.shape == want.shape and got.dtype == want.dtype == np.float32
    zero = want == 0
    np.testing.assert_array_equal(got[zero], 0.0)
    np.testing.assert_array_equal(got.view(np.uint32)[~zero], want.view(np.uint32)[~zero])


@pytest.mark.parametrize("shape", [(300,), (31, 3, 2), (2, 265)],
                         ids=["one-column", "carried-columns", "vmapped-pair"])
@pytest.mark.parametrize("infs", ["neg", "pos", "both", "none"])
def test_onehot_rows_matches_take(infs, shape):
    """``_onehot_rows`` with the infinity split gives ``jnp.take``'s values
    for tables holding -inf, +inf, both or neither, next to ±0.0 and the
    largest finite magnitudes: one column, several carried columns, and a
    vmapped pair sharing its row codes.  A finite table also takes the
    plain contraction (SUM/COUNT's) to the same values.  The tropical
    plans end to end: the ``tropical_min``/``tropical_max`` cases of the
    lane-dense plan tests above."""
    from repro.core import plans as plans_mod

    table = _gather_table(shape, infs, seed=len(shape) + len(infs))
    vmapped = shape == (2, 265)
    d = shape[1] if vmapped else shape[0]
    codes = np.random.default_rng(7).integers(0, d, 4096).astype(np.int32)
    axis = 1 if vmapped else 0
    want = np.moveaxis(np.take(table, codes, axis=axis), axis, -1)

    def gather(infinities):
        def one(t):
            return plans_mod._onehot_rows(t, jnp.asarray(codes), infinities)
        f = jax.vmap(one) if vmapped else one
        return np.asarray(jax.jit(f)(jnp.asarray(table)))

    _assert_same_bits(gather(True), want)
    if infs == "none":
        _assert_same_bits(gather(False), want)
