"""TreantServer (ISSUE 8): multi-tenant serving-tier invariants.

The correctness spine: N sessions served through one ``TreantServer`` —
with micro-batching, coalescing, cross-session batched fan-out, a shared
prefetch pool and a global store byte budget — must produce per-session
results **bit-identical** to the same event sequences applied serially on
private single-session Treants.  Everything the server shares (messages,
vmapped dispatches, deduped executions, evicted-and-recomputed entries) is
an optimization, never a semantic.

Plus the concurrency satellites: watermark reads stay un-torn across
server-driven background flushes, ``commit_log`` trims only unpinned
snapshots, eviction never drops pinned/in-flight entries, one session's
close never drops store entries a sibling still references, and
per-relation compaction thresholds follow the learned delete mix.
"""

import numpy as np
import pytest

import repro.core  # noqa: F401 — import order (core before relational)
from repro.core import DashboardSpec, Treant, VizSpec
from repro.core import semiring as sr
from repro.core.dashboard import ClearFilter, SetFilter, Undo
from repro.relational.relation import Catalog, Relation
from repro.serve import QueueFull, TreantServer

from test_stream_ingest import (
    assert_factors_identical,
    fact_batch,
    spec_for,
    star_catalog,
)


def brush(lo: int, hi: int) -> SetFilter:
    return SetFilter(attr="a", lo=lo, hi=hi, source="by_c")


def drain(server: TreantServer) -> None:
    while server.queue_depth:
        server.step()


# ---------------------------------------------------------------------------
# cross-session batched fan-out ≡ serial per-session apply (bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_name", ["sum", "tropical_min", "moments"])
def test_cross_session_fanout_matches_serial_apply(ring_name):
    """8 sessions over one shared spec, brushing a mix of shared and distinct
    σ values, drained through cross-session micro-batches: every session's
    every viz must equal a serial apply of its own event on a private
    Treant — and at least one dispatch must have served >1 session."""
    spec = spec_for(ring_name)
    t = Treant(star_catalog(), ring=sr.get(ring_name), use_plans=True)
    server = TreantServer(t)
    events = [brush(i % 4, i % 4 + 3) for i in range(8)]  # 4 shared σ, 2 each
    handles = [server.open_session(spec, name=f"s{i}") for i in range(8)]
    for h, ev in zip(handles, events):
        h.submit(ev)
    drain(server)
    assert t.cache_stats()["serve"]["cross_session_batch_width"] > 1
    for h, ev in zip(handles, events):
        ref_t = Treant(star_catalog(), ring=sr.get(ring_name), use_plans=True)
        ref = ref_t.open_session(spec, name="ref")
        ref.apply(ev)
        for viz in ("by_c", "by_d"):
            assert_factors_identical(
                h.read(viz).factor, ref.read(viz).factor
            )


def test_followup_brushes_and_multi_event_sequences_match_serial():
    """Several batches deep (brush → re-brush → clear → undo), per-session
    state stays exactly what a serial apply loop would produce."""
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    seqs = {
        "s0": [brush(0, 3), brush(4, 7), ClearFilter(attr="a")],
        "s1": [brush(2, 5), Undo(), brush(6, 9)],
        "s2": [brush(0, 3), brush(0, 3)],  # idempotent re-brush
    }
    handles = {sid: server.open_session(spec, name=sid) for sid in seqs}
    # interleave: one event per session per round, drained between rounds
    for rnd in range(3):
        for sid, seq in seqs.items():
            if rnd < len(seq):
                handles[sid].submit(seq[rnd])
        drain(server)
    for sid, seq in seqs.items():
        ref_t = Treant(star_catalog(), use_plans=True)
        ref = ref_t.open_session(spec, name="ref")
        for ev in seq:
            ref.apply(ev)
        for viz in ("by_c", "by_d"):
            assert_factors_identical(
                handles[sid].read(viz).factor, ref.read(viz).factor
            )


# ---------------------------------------------------------------------------
# event queue: coalescing, fairness, backpressure
# ---------------------------------------------------------------------------

def test_superseded_events_coalesce_and_are_never_executed():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    h = server.open_session(spec, name="s")
    for lo in range(5):  # five brush positions queued back-to-back
        h.submit(brush(lo, lo + 3))
    assert server.queue_depth == 1, "stale brush positions must coalesce away"
    assert server.stats_.coalesced_events == 4
    drain(server)
    # only the LAST position was executed
    ref_t = Treant(star_catalog(), use_plans=True)
    ref = ref_t.open_session(spec, name="ref")
    ref.apply(brush(4, 7))
    assert_factors_identical(h.read("by_d").factor, ref.read("by_d").factor)
    assert server.stats_.events_processed == 1


def test_queued_undo_blocks_coalescing():
    """Each applied event pushes an undo snapshot, so once an Undo is queued
    the earlier brush must NOT be coalesced away (it changes what the Undo
    reverts to)."""
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    h = server.open_session(spec, name="s")
    h.submit(brush(0, 3))
    h.submit(Undo())
    h.submit(brush(4, 7))
    assert server.queue_depth == 3
    drain(server)
    ref_t = Treant(star_catalog(), use_plans=True)
    ref = ref_t.open_session(spec, name="ref")
    for ev in (brush(0, 3), Undo(), brush(4, 7)):
        ref.apply(ev)
    assert_factors_identical(h.read("by_d").factor, ref.read("by_d").factor)


def test_micro_batch_fairness_one_event_per_session():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    ha = server.open_session(spec, name="a")
    hb = server.open_session(spec, name="b")
    # a bursty session queues order-sensitive events (no coalescing)
    from repro.core.dashboard import Drill, Rollup
    ha.submit(Drill(viz="by_c", attr="d"))
    ha.submit(Rollup(viz="by_c", attr="d"))
    ha.submit(Drill(viz="by_c", attr="e"))
    hb.submit(brush(0, 3))
    n = server.step()
    # first batch: one event from each session, not three from the burster
    assert n == 2
    assert server.stats_.batches == 1
    drain(server)
    assert server.stats_.events_processed == 4


def test_backpressure_reject_raises_and_drain_makes_room():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t, max_queue=2, backpressure="reject")
    h = server.open_session(spec, name="s")
    from repro.core.dashboard import Drill
    h.submit(Drill(viz="by_c", attr="d"))
    h.submit(Drill(viz="by_c", attr="e"))
    with pytest.raises(QueueFull):
        h.submit(Drill(viz="by_d", attr="e"))
    assert server.stats_.rejected_events == 1
    drain(server)

    t2 = Treant(star_catalog(), use_plans=True)
    server2 = TreantServer(t2, max_queue=2, backpressure="drain")
    h2 = server2.open_session(spec, name="s")
    h2.submit(Drill(viz="by_c", attr="d"))
    h2.submit(Drill(viz="by_c", attr="e"))
    h2.submit(Drill(viz="by_d", attr="e"))  # forces a synchronous drain
    assert server2.stats_.backpressure_drains == 1
    assert server2.queue_depth <= 2
    drain(server2)
    assert server2.stats_.events_processed == 3


# ---------------------------------------------------------------------------
# global byte budget: priority eviction, pinned/in-flight exemption,
# bit-identical recomputation
# ---------------------------------------------------------------------------

def _run_brush_storm(max_store_bytes=None, sessions=6, ring_name="sum"):
    spec = spec_for(ring_name)
    t = Treant(star_catalog(), ring=sr.get(ring_name), use_plans=True)
    server = TreantServer(t, max_store_bytes=max_store_bytes)
    handles = [server.open_session(spec, name=f"s{i}") for i in range(sessions)]
    for rnd in range(4):
        for i, h in enumerate(handles):
            h.submit(brush((rnd + i) % 9, (rnd + i) % 9 + 3))
        drain(server)
    return t, server, handles


def test_byte_budget_stays_under_budget_and_reads_bit_identical():
    # unbudgeted footprint first
    t_free, _, free_handles = _run_brush_storm(None)
    unbudgeted = t_free.store.nbytes
    refs = {
        (h.id, viz): h.read(viz).factor
        for h in free_handles for viz in ("by_c", "by_d")
    }
    t, server, handles = _run_brush_storm(max_store_bytes=unbudgeted // 2)
    store = t.store
    assert store.evictions > 0, "a 50% budget must actually evict"
    # pinned entries are the floor no budget may cross; above it, the store
    # must respect the budget once every dispatch has closed
    assert store.nbytes - store.pinned_nbytes <= store.max_bytes
    for sig in store._pinned:
        assert sig in store._data, f"pinned entry {sig} was evicted"
    assert store._inflight_depth == 0 and not store._inflight
    # every read recomputes evicted entries on demand, bit-identically
    for h in handles:
        for viz in ("by_c", "by_d"):
            assert_factors_identical(
                h.read(viz).factor, refs[(h.id, viz)]
            )


def test_inflight_entries_survive_eviction_inside_a_dispatch():
    """Force a budget so tight every put overflows: the messages a dispatch
    itself just materialized (in-flight) must not be evicted out from under
    it — the dispatch completes and returns the correct result."""
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    ref_t = Treant(star_catalog(), use_plans=True)
    ref = ref_t.open_session(spec, name="ref")
    server = TreantServer(t, max_store_bytes=1)  # absurdly tight
    h = server.open_session(spec, name="s")
    h.submit(brush(2, 5))
    drain(server)
    ref.apply(brush(2, 5))
    for viz in ("by_c", "by_d"):
        assert_factors_identical(h.read(viz).factor, ref.read(viz).factor)


# ---------------------------------------------------------------------------
# Session.close under sharing: consumer refcounts
# ---------------------------------------------------------------------------

def test_close_does_not_drop_entries_a_live_session_references():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    ha = server.open_session(spec, name="a")
    hb = server.open_session(spec, name="b")
    # a brushes first (produces the σ messages), b brushes the same σ later
    # (per-viz dispatch so b genuinely HITS a's tagged entries)
    ha.submit(brush(1, 4))
    drain(server)
    hb.submit(brush(1, 4))
    drain(server)
    owned_by_a = {
        s for s, owner in t.store._producer.items() if owner.startswith("a:")
    }
    shared = {s for s in owned_by_a if "b" in t.store._users.get(s, set())}
    assert shared, "b must have been recorded as a consumer of a's entries"
    ha.close()
    for sig in shared:
        assert sig in t.store._data, (
            "closing the producer dropped an entry a live session references"
        )
        assert t.store._producer[sig].startswith("b:"), (
            "ownership must pass to the surviving reader"
        )
    # warm re-read for b: no recomputation of the shared messages
    r = hb.read("by_d")
    assert r.stats.messages_computed == 0
    # now b closes too: with no surviving reader the entries finally drop
    hb.close()
    for sig in shared:
        assert sig not in t.store._data


def test_interleaved_open_close_cycles_stay_consistent():
    """Open/close churn with shared brushes: reads on live sessions stay
    bit-identical to serial, pins never leak, producers never dangle."""
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    ref_t = Treant(star_catalog(), use_plans=True)
    ref = ref_t.open_session(spec, name="ref")
    ref.apply(brush(3, 6))
    live = {}
    for cycle in range(4):
        sid = f"g{cycle}"
        live[sid] = server.open_session(spec, name=sid)
        live[sid].submit(brush(3, 6))
        drain(server)
        if cycle % 2 == 1:  # close the *previous* session, keep this one
            prev = f"g{cycle - 1}"
            live.pop(prev).close()
        for h in live.values():
            assert_factors_identical(
                h.read("by_d").factor, ref.read("by_d").factor
            )
    for h in list(live.values()):
        h.close()
    assert len(server.sessions) == 0
    # no dangling producer tags for closed sessions' entries
    closed = {f"g{c}:" for c in range(4)}
    for sig, owner in t.store._producer.items():
        assert sig in t.store._data
        assert not any(owner.startswith(p) for p in closed) or sig in t.store._pinned


# ---------------------------------------------------------------------------
# commit_log retention + snapshot-read pinning
# ---------------------------------------------------------------------------

def test_commit_log_trims_unpinned_but_keeps_pinned_snapshots():
    cat = star_catalog()
    cat.commit_retention = 8
    t = Treant(cat, use_plans=False, compaction_threshold=0.0)
    rng = np.random.default_rng(3)
    # pin the snapshot an imaginary long-running reader holds
    pinned_wm = cat.pin_watermark()
    pinned_snapshot = dict(cat._latest)
    for _ in range(20):
        buf = t.stream("F")
        codes, meas = fact_batch(rng, cat, 5)
        buf.append(codes, measures=meas)
        t.flush()
    # retention exceeded, but the pinned snapshot (and everything after it)
    # must survive
    logged = {wm: snap for wm, snap in cat.commit_log}
    assert pinned_wm in logged and logged[pinned_wm] == pinned_snapshot
    assert len(cat.commit_log) > cat.commit_retention
    cat.release_watermark(pinned_wm)
    assert len(cat.commit_log) <= cat.commit_retention
    assert pinned_wm not in dict(cat.commit_log)


def test_snapshot_read_context_and_refcounted_pins():
    cat = star_catalog()
    cat.commit_retention = 2
    t = Treant(cat, use_plans=False, compaction_threshold=0.0)
    rng = np.random.default_rng(4)
    with cat.snapshot_read() as (wm, versions):
        w2 = cat.pin_watermark(wm)  # second holder of the same mark
        assert w2 == wm
        for _ in range(6):
            buf = t.stream("F")
            codes, meas = fact_batch(rng, cat, 5)
            buf.append(codes, measures=meas)
            t.flush()
        assert wm in dict(cat.commit_log)
        assert dict(cat.commit_log)[wm] == versions
    # context exited but the second pin still holds
    assert wm in dict(cat.commit_log)
    cat.release_watermark(wm)
    assert wm not in dict(cat.commit_log)
    assert len(cat.commit_log) <= cat.commit_retention


def test_server_sessions_pin_their_read_watermark_across_ticks():
    spec = spec_for("sum")
    cat = star_catalog()
    cat.commit_retention = 2
    t = Treant(cat, use_plans=True, compaction_threshold=0.0)
    server = TreantServer(t)
    h = server.open_session(spec, name="s")
    opened_at = h._pinned_wm
    rng = np.random.default_rng(5)
    for _ in range(5):
        buf = t.stream("F")
        codes, meas = fact_batch(rng, cat, 5)
        buf.append(codes, measures=meas)
        t.flush()  # caller-thread flush: the session does not participate
    assert opened_at in dict(cat.commit_log), (
        "trimming dropped the snapshot a server session still holds"
    )
    # the session interacts → its pin advances, the old snapshot trims
    h.submit(brush(0, 3))
    drain(server)
    assert h._pinned_wm == cat.watermark
    assert opened_at not in dict(cat.commit_log)
    h.close()


# ---------------------------------------------------------------------------
# per-relation compaction thresholds (learned delete mix)
# ---------------------------------------------------------------------------

def test_compaction_policy_learns_per_relation_delete_mix():
    from repro.relational.stream import CompactionPolicy

    pol = CompactionPolicy()
    base = 0.25
    assert pol.threshold("F", base) == base  # no observations yet
    for _ in range(8):
        pol.observe("heavy", n_app=1, n_del=9)   # delete-heavy
        pol.observe("light", n_app=9, n_del=1)   # append-mostly
    assert pol.threshold("heavy", base) < base < pol.threshold("light", base)
    assert pol.threshold("heavy", base) >= base * 0.5
    assert pol.threshold("light", base) <= min(0.9, base * 1.5)
    assert pol.threshold("anything", 0.0) == 0.0  # disabled stays disabled


def test_delete_heavy_relation_compacts_earlier_than_append_mostly():
    """Same tombstone fraction, different learned mixes: the delete-heavy
    relation crosses its (tightened) threshold first."""
    cat = star_catalog(n_fact=400)
    t = Treant(cat, ring=sr.SUM, use_plans=False, compaction_threshold=0.25)
    rng = np.random.default_rng(9)
    compacted: dict[str, int] = {}
    for tick in range(12):
        buf = t.stream("F")
        # delete-heavy mix on F: few appends, many deletes
        codes, meas = fact_batch(rng, cat, 4)
        buf.append(codes, measures=meas)
        live = np.flatnonzero(buf.base._materialized_weights() != 0.0)
        mask = np.zeros(buf.base.num_rows + buf.pending_appends, bool)
        mask[rng.choice(live, 20, replace=False)] = True
        buf.delete(mask)
        res = t.flush()
        for c in res.compactions:
            compacted.setdefault(c.relation, tick)
    assert "F" in compacted, "delete-heavy relation never compacted"
    thr = t.compaction_policy.threshold("F", t.compaction_threshold)
    assert thr < t.compaction_threshold, (
        "learned threshold should be tighter than the base for delete-heavy"
    )


# ---------------------------------------------------------------------------
# server-driven think-time: background flush, scheduler drain, shared pool
# ---------------------------------------------------------------------------

def test_idle_runs_background_flush_and_unturn_watermark_reads():
    """Streaming ingest moves off the caller thread: events + idle() ticks
    interleave, and every session's post-tick read equals a cold rebuild
    over the committed versions (no torn/stale state)."""
    spec = spec_for("sum")
    cat = star_catalog()
    t = Treant(cat, use_plans=True, compaction_threshold=0.0)
    server = TreantServer(t)
    handles = [server.open_session(spec, name=f"s{i}") for i in range(3)]
    rng = np.random.default_rng(11)
    for rnd in range(3):
        buf = t.stream("F")
        codes, meas = fact_batch(rng, cat, 10)
        buf.append(codes, measures=meas)
        for i, h in enumerate(handles):
            h.submit(brush((rnd + i) % 6, (rnd + i) % 6 + 3))
        drain(server)
        assert buf.has_pending  # nothing flushed on the event path
        server.idle()           # ← background tick happens HERE
        assert not buf.has_pending
        for h in handles:
            q = h.query_of("by_d")
            assert q.version_of("F") == cat.latest_version("F")
            eng = t.engine_for(q.ring_name, q.measure)
            cold = Treant(
                Catalog([cat.get(n) for n in cat.names()]), use_plans=False
            )
            ref, _ = cold.engine.execute(
                q.with_version("F", cat.latest_version("F"))
            )
            assert_factors_identical(h.read("by_d").factor, ref)
    assert server.stats_.background_flushes == 3


def test_idle_drains_think_time_and_shared_pool_serves_sibling_sessions():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t, speculate=4)
    ha = server.open_session(spec, name="a")
    hb = server.open_session(spec, name="b")
    ha.submit(brush(3, 6))
    drain(server)
    server.idle()  # speculate around a's brush → shared pool
    assert len(server._pool) > 0
    # b brushes a NEIGHBOR window a never executed — (6,9) is a's first
    # speculation candidate (ranges shift by whole widths) — and is served
    # from the pool that a's think-time filled
    before = server.stats_.shared_prefetch_hits
    hb.submit(brush(6, 9))
    drain(server)
    assert server.stats_.shared_prefetch_hits > before
    # and the pool-served result is still bit-identical to serial
    ref_t = Treant(star_catalog(), use_plans=True)
    ref = ref_t.open_session(spec, name="ref")
    ref.apply(brush(6, 9))
    assert_factors_identical(hb.read("by_d").factor, ref.read("by_d").factor)


def test_pool_eviction_keeps_just_hit_entry_fifo_would_drop():
    """Regression: the shared pool evicted in plain insertion order, so the
    OLDEST entry went first even when it was the one just served to a
    sibling session.  A hit must refresh recency: after overflowing the
    pool, the just-hit digest survives while cold never-read entries go."""
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t, speculate=4, pool_capacity=4)
    ha = server.open_session(spec, name="a")
    hb = server.open_session(spec, name="b")
    ha.submit(brush(3, 6))
    drain(server)
    server.idle()  # a's speculations fill the pool (oldest first)
    assert len(server._pool) > 0
    oldest = next(iter(server._pool))  # insertion-oldest = FIFO's victim
    # (6,9) is a's first speculation candidate for brush(3,6) — the oldest
    # pool entry — and b hits it
    hb.submit(brush(6, 9))
    drain(server)
    assert server.stats_.shared_prefetch_hits > 0
    hit_digest = [d for d, p in server._pool.items() if p.hot]
    assert hit_digest == [oldest]  # b hit exactly the FIFO victim
    # now overflow the pool: more speculation around new brushes
    ha.submit(brush(0, 3))
    hb.submit(brush(12, 15))
    drain(server)
    server.idle()
    assert server.stats_.pool_evictions > 0
    assert oldest in server._pool  # FIFO would have popped it first


def test_pool_eviction_orders_by_cost_and_never_drops_hot_entries():
    """Unit check of the eviction policy itself: cheapest non-hot entry of
    the cold window goes first; hot (hit-this-batch) entries are exempt even
    when they are both the oldest and the cheapest; an all-hot pool admits
    over capacity rather than dropping a shielded entry."""
    import types

    from repro.serve.server import _Pooled

    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t, pool_capacity=3)
    costs = {"d1": 0.0, "d2": 1.0, "d3": 4.0, "d4": 2.0, "d5": 3.0}
    for d, c in costs.items():
        server._pool[d] = _Pooled(None, None, cost=c, hot=(d == "d1"))
    server._absorb_prefetch(types.SimpleNamespace(_prefetched={}))
    # d1 is oldest AND cheapest, but hot → kept; d2 (cost 1) and d4 (cost 2)
    # are the two cheapest cold entries → evicted
    assert set(server._pool) == {"d1", "d3", "d5"}
    assert server.stats_.pool_evictions == 2
    for p in server._pool.values():
        p.hot = True
    server._pool["d6"] = _Pooled(None, None, cost=0.0, hot=True)
    server._absorb_prefetch(types.SimpleNamespace(_prefetched={}))
    assert len(server._pool) == 4  # over capacity: every entry is shielded
    assert server.stats_.pool_evictions == 2


def test_serve_counters_surface_in_cache_stats():
    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t, max_store_bytes=1 << 20)
    h = server.open_session(spec, name="s")
    h.submit(brush(0, 3))
    drain(server)
    st = t.cache_stats()["serve"]
    for key in (
        "queue_depth", "coalesced_events", "cross_session_batch_width",
        "store_evictions", "bytes_held", "bytes_pinned", "byte_budget",
        "sessions", "events_processed", "batches",
    ):
        assert key in st, key
    assert st["sessions"] == 1 and st["events_processed"] == 1
    assert st["byte_budget"] == 1 << 20


def test_queue_wait_grows_by_the_wait_of_each_processed_event():
    """``queue_wait_s`` adds, for every event a step processes, the time
    from its ``submit`` until the step drained its batch."""
    import time

    spec = spec_for("sum")
    t = Treant(star_catalog(), use_plans=True)
    server = TreantServer(t)
    a, b = (server.open_session(spec, name=n) for n in ("a", "b"))
    t0 = time.perf_counter()
    a.submit(brush(0, 3))
    time.sleep(0.05)
    b.submit(brush(1, 4))
    assert server.stats_.queue_wait_s == 0.0
    server.step()
    elapsed = time.perf_counter() - t0
    st = t.cache_stats()["serve"]
    assert st["events_processed"] == 2
    # a waited the 50 ms sleep at least; neither waited past the step
    assert 0.05 <= st["queue_wait_s"] <= 2 * elapsed
    before = st["queue_wait_s"]
    a.submit(brush(2, 5))
    server.step()
    assert server.stats_.queue_wait_s > before
