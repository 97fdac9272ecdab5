"""Hierarchical memory tier (``repro.mem`` + ``MemPlan``).

Covers the three-tier contract end to end: hot-LRU evictions demote into
the cold arena, a cold hit serves from one arena read (bit-identical to
recompute, no stage-1 call, no device slot), the async worker promotes
only frequency-qualified users back to hot, and the bulk warming feed
makes a warmed user's first live request a cold hit. Plus the arena's
budget/no-leak invariants, the promotion policy in isolation, the
``UserRepCache`` removal-record contract (fired outside the lock), and
the ``MemPlan`` validation rows.
"""
import threading

import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.mem import ColdRepStore, PromotionWorker, RepWarmer
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.serve import MemPlan, ServePlan, ServeRequest, ServingEngine
from repro.serve.cache import UserRepCache
from repro.serve.plan import PlanError, PlanResolutionWarning


@pytest.fixture(scope="module")
def paper():
    graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.05))
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    return graph, params, user_in


def _request(graph, user_in, uid, n=8, seed=None, version=0):
    feeds = make_recsys_feeds(
        graph, n, jax.random.PRNGKey(uid if seed is None else seed))
    return ServeRequest(
        user_id=uid,
        user_feeds={k: v for k, v in feeds.items() if k in user_in},
        candidate_feeds={k: v for k, v in feeds.items() if k not in user_in},
        feature_version=version)


def _cold_plan(**overrides):
    base = dict(cache__max_cached_users=2, mem__cold_tier=True,
                mem__cold_bytes=1 << 22, mem__promote_touches=2,
                batch__linger_ms=0.0)
    base.update(overrides)
    return ServePlan().evolve(**base)


def _reps(uid, d=8):
    return {"a": np.full((1, d), float(uid), np.float32),
            "b": np.full((1, 2, 3), uid, np.int32)}


# -- ColdRepStore ------------------------------------------------------------
class TestColdRepStore:
    def test_round_trip_bit_exact(self):
        cold = ColdRepStore(1 << 16)
        reps = {"a": np.arange(8, dtype=np.float32)[None] * 0.3,
                "b": np.ones((1, 2, 3), np.int32)}
        cold.put((1, 0), reps)
        got = cold.get((1, 0))
        for k in reps:
            assert got[k].shape == reps[k].shape
            assert got[k].dtype == reps[k].dtype
            assert np.array_equal(got[k], reps[k])
        # reads hand back COPIES: mutating one must not poison the arena
        got["a"][:] = -1
        assert np.array_equal(cold.get((1, 0))["a"], reps["a"])

    def test_stale_version_dropped_not_served(self):
        cold = ColdRepStore(1 << 16)
        cold.put((1, 0), _reps(1))
        assert cold.get((1, 7)) is None
        assert (1, 0) not in cold          # stale entry dropped outright
        assert cold.stats()["misses"] == 1

    def test_budget_overflow_evicts_lru_without_leaking_slabs(self):
        per_user = 8 * 4 + 2 * 3 * 4       # bytes of _reps rows
        cold = ColdRepStore(cold_bytes=10 * per_user, slab_rows=4)
        for u in range(50):
            cold.put((u, 0), _reps(u))
        st = cold.stats()
        assert st["capacity"] == 10
        assert st["users"] == 10
        assert st["evictions"] == 40
        # the no-leak invariant: slabs are bounded by ceil(capacity /
        # slab_rows) FOREVER — churn recycles rows in place
        assert st["slabs"] == 3
        assert st["slab_bytes"] <= 3 * 4 * per_user
        # survivors are the 10 most recent, values intact
        for u in range(40, 50):
            assert cold.get((u, 0))["a"][0, 0] == float(u)
        for u in range(40):
            assert cold.get((u, 0)) is None

    def test_lru_refresh_on_get(self):
        cold = ColdRepStore(cold_bytes=3 * (8 * 4 + 2 * 3 * 4))
        for u in range(3):
            cold.put((u, 0), _reps(u))
        cold.get((0, 0))                   # refresh user 0
        cold.put((3, 0), _reps(3))         # evicts user 1, not 0
        assert (0, 0) in cold and (1, 0) not in cold

    def test_layout_drift_rejected(self):
        cold = ColdRepStore(1 << 16)
        cold.put((1, 0), _reps(1))
        with pytest.raises(ValueError, match="layout"):
            cold.put((2, 0), {"a": np.zeros((1, 9), np.float32),
                              "b": np.zeros((1, 2, 3), np.int32)})
        with pytest.raises(ValueError, match="leading dim 1"):
            cold.put((2, 0), {"a": np.zeros((2, 8), np.float32),
                              "b": np.zeros((2, 2, 3), np.int32)})


# -- PromotionWorker ---------------------------------------------------------
class TestPromotionWorker:
    def test_k_touches_within_window_promotes(self):
        cold = ColdRepStore(1 << 16)
        cache = UserRepCache(max_users=8)
        t = [0.0]
        pw = PromotionWorker(cold, cache, touches=3, window_s=5.0,
                             clock=lambda: t[0])
        try:
            cold.put((1, 0), _reps(1))
            for i in range(2):
                pw.touch((1, 0))
            pw.flush()
            assert (1, 0) not in cache     # below threshold
            pw.touch((1, 0))
            pw.flush()
            assert (1, 0) in cache
            assert pw.promotions == 1
            # promoted copy is bit-identical to the arena row
            assert np.array_equal(cache.get((1, 0))["a"], _reps(1)["a"])
        finally:
            pw.stop()

    def test_window_expiry_resets_tail_users(self):
        cold = ColdRepStore(1 << 16)
        cache = UserRepCache(max_users=8)
        t = [0.0]
        pw = PromotionWorker(cold, cache, touches=2, window_s=5.0,
                             clock=lambda: t[0])
        try:
            cold.put((1, 0), _reps(1))
            pw.touch((1, 0))
            pw.flush()                     # process BEFORE moving the clock
            t[0] = 10.0                    # first touch now outside window
            pw.touch((1, 0))
            pw.flush()
            assert (1, 0) not in cache     # one-shot-per-window: no promote
            pw.touch((1, 0))
            pw.flush()
            assert (1, 0) in cache         # two touches at t=10: promoted
        finally:
            pw.stop()

    def test_vanished_cold_row_is_a_noop(self):
        cold = ColdRepStore(1 << 16)
        cache = UserRepCache(max_users=8)
        pw = PromotionWorker(cold, cache, touches=1, window_s=60.0)
        try:
            pw.touch((9, 0))               # never put into cold
            pw.flush()
            assert (9, 0) not in cache and pw.promotions == 0
        finally:
            pw.stop()


# -- UserRepCache removal records --------------------------------------------
class TestCacheRemovalRecords:
    def test_records_carry_reason_and_reps(self):
        cache = UserRepCache(max_users=2)
        seen = []
        cache.subscribe_removal(
            lambda uid, ver, reps, reason: seen.append((uid, ver, reason)))
        cache.put((1, 0), _reps(1))
        cache.put((2, 0), _reps(2))
        cache.put((3, 0), _reps(3))        # evicts user 1 (LRU)
        cache.put((2, 1), _reps(2))        # supersedes user 2's version 0
        cache.invalidate_user(3)
        cache.clear()
        assert seen == [(1, 0, "evict"), (2, 0, "supersede"),
                        (3, 0, "invalidate"), (2, 1, "clear")]

    def test_eviction_record_reps_are_the_cached_values(self):
        cache = UserRepCache(max_users=1)
        got = []
        cache.subscribe_removal(
            lambda uid, ver, reps, reason: got.append(reps))
        r1 = _reps(1)
        cache.put((1, 0), r1)
        cache.put((2, 0), _reps(2))
        assert len(got) == 1
        assert got[0] is r1                # the exact cached mapping

    def test_listeners_fire_outside_the_cache_lock(self):
        """The demote path (and any listener) may take other locks — so
        the cache lock must NOT be held while listeners run. Probe it:
        a non-blocking acquire inside the callback must succeed."""
        cache = UserRepCache(max_users=1)
        lock_free = []

        def probe(uid, ver, reps, reason):
            ok = cache._lock.acquire(blocking=False)
            if ok:
                cache._lock.release()
            lock_free.append(ok)

        cache.subscribe_removal(probe)
        cache.put((1, 0), _reps(1))
        cache.put((2, 0), _reps(2))        # evict -> probe fires
        cache.invalidate_user(2)           # invalidate -> probe fires
        assert lock_free == [True, True]

    def test_legacy_uid_only_subscribers_still_work(self):
        cache = UserRepCache(max_users=1)
        uids = []
        cache.subscribe(uids.append)
        cache.put((1, 0), _reps(1))
        cache.put((2, 0), _reps(2))        # evicts user 1
        assert uids == [1]


# -- engine integration ------------------------------------------------------
class TestEngineMemTier:
    @pytest.mark.parametrize("mode", ["vani", "uoi", "mari"])
    def test_demote_promote_round_trip_bit_identical(self, paper, mode):
        """Eviction churn pushes a user to cold; the cold-served scores,
        the post-promotion hot-served scores, and a cache-off engine's
        recompute must all be bit-identical."""
        graph, params, user_in = paper
        plan = _cold_plan(graph__mode=mode)
        eng = ServingEngine(graph, params, plan=plan)
        off = ServingEngine(graph, params, plan=ServePlan().evolve(
            graph__mode=mode, cache__cache_user_reps=False,
            batch__linger_ms=0.0))
        try:
            if mode == "vani":
                # single-stage: no stage-1 outputs to tier — the cold
                # tier disarms (same forcing as cache_user_reps) and
                # serving works unchanged
                assert not eng.cold_tier
                r = eng.score(_request(graph, user_in, 0))
                assert not r.cold_hit
                return
            assert eng.cold_tier
            r0 = _request(graph, user_in, 0)
            base = eng.score(r0)
            # churn users 1..2 through the 2-slot hot LRU: user 0 demotes
            for u in (1, 2):
                eng.score(_request(graph, user_in, u))
            assert eng.demotions >= 1
            s1 = eng.stage1_calls
            cold = eng.score(r0)
            assert cold.cold_hit and not cold.user_cache_hit
            assert eng.stage1_calls == s1          # no recompute
            assert np.array_equal(cold.scores, base.scores)
            # second touch inside the window qualifies the promotion
            eng.score(r0)
            eng.flush_promotions()
            hot = eng.score(r0)
            assert hot.user_cache_hit and not hot.cold_hit
            assert np.array_equal(hot.scores, base.scores)
            # ... and everything equals the cache-off recompute
            assert np.array_equal(off.score(r0).scores, base.scores)
        finally:
            eng.close()
            off.close()

    def test_cold_hit_skips_device_tier(self, paper):
        """A cold-served (by policy, tail) user must not cost a device
        slot: its packs take the bit-identical re-stacking fallback."""
        graph, params, user_in = paper
        plan = _cold_plan(cache__device_resident=True,
                          cache__device_slots=4)
        eng = ServingEngine(graph, params, plan=plan)
        try:
            r0 = _request(graph, user_in, 0)
            base = eng.score(r0)
            for u in (1, 2):
                eng.score(_request(graph, user_in, u))
            writes = eng._device_store.stats()["writes"]
            cold = eng.score(r0)
            assert cold.cold_hit
            assert eng._device_store.stats()["writes"] == writes
            assert np.array_equal(cold.scores, base.scores)
        finally:
            eng.close()

    def test_warm_then_serve_first_request_hits(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_cold_plan())
        off = ServingEngine(graph, params, plan=ServePlan().evolve(
            cache__cache_user_reps=False, batch__linger_ms=0.0))
        try:
            reqs = [_request(graph, user_in, u) for u in range(5, 9)]
            n = eng.warm([(r.user_id, r.user_feeds) for r in reqs])
            assert n == len(reqs)
            s1 = eng.stage1_calls
            for r in reqs:
                res = eng.score(r)
                assert res.cold_hit, "warmed user's FIRST request must hit"
                assert np.array_equal(res.scores, off.score(r).scores)
            assert eng.stage1_calls == s1
            assert eng.mem_stats()["warm"]["warmed"] == len(reqs)
        finally:
            eng.close()
            off.close()

    def test_invalidate_drops_warmed_only_user(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_cold_plan())
        try:
            r = _request(graph, user_in, 11)
            eng.warm([(r.user_id, r.user_feeds)])
            eng.invalidate_user(11)
            res = eng.score(r)
            assert not res.cold_hit and not res.user_cache_hit
        finally:
            eng.close()

    def test_version_bump_misses_cold(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_cold_plan())
        try:
            eng.score(_request(graph, user_in, 0))
            for u in (1, 2):
                eng.score(_request(graph, user_in, u))   # demote user 0
            res = eng.score(_request(graph, user_in, 0, version=1))
            assert not res.cold_hit        # stale version never served
        finally:
            eng.close()

    def test_mem_gauges_and_instants(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params,
                            plan=_cold_plan(obs__trace=True))
        try:
            r0 = _request(graph, user_in, 0)
            eng.score(r0)
            for u in (1, 2):
                eng.score(_request(graph, user_in, u))
            eng.score(r0)
            eng.score(r0)
            eng.flush_promotions()
            eng.score(r0)
            eng.warm([(5, _request(graph, user_in, 5).user_feeds)])
            names = {e[1] for e in eng.tracer.events()}
            assert {"cold_hit", "cold_miss", "promote", "demote",
                    "warm"} <= names
            snap = eng.metrics.snapshot()
            assert snap["cold_hits"] >= 2
            assert snap["demotions"] >= 1
            assert snap["promotions"] >= 1
            assert snap["warmed_users"] == 1
            assert snap["cold_users"] >= 1
        finally:
            eng.close()

    def test_warm_requires_cold_tier(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan())
        try:
            with pytest.raises(RuntimeError, match="cold_tier"):
                eng.warm([(0, _request(graph, user_in, 0).user_feeds)])
        finally:
            eng.close()


# -- MemPlan -----------------------------------------------------------------
class TestMemPlan:
    def test_defaults_off_and_round_trip(self):
        p = ServePlan()
        assert p.mem == MemPlan()
        assert not p.mem.cold_tier
        p2 = p.evolve(mem__cold_tier=True, mem__cold_bytes=1 << 20,
                      mem__promote_touches=3, mem__promote_window_s=5.0,
                      mem__warm_batch=64)
        assert ServePlan.from_json(p2.to_json()) == p2

    @pytest.mark.parametrize("field,value", [
        ("cold_bytes", 0), ("promote_touches", 0),
        ("promote_window_s", 0.0), ("warm_batch", 0)])
    def test_non_positive_knobs_reject(self, field, value):
        with pytest.raises(PlanError, match=field):
            ServePlan().evolve(**{f"mem__{field}": value})

    def test_type_contract(self):
        with pytest.raises(PlanError, match="cold_bytes"):
            ServePlan(mem={"cold_bytes": "256MiB"})

    def test_cold_tier_without_cache_resolves_off(self):
        with pytest.warns(PlanResolutionWarning, match="cold_tier"):
            p = ServePlan().evolve(cache__cache_user_reps=False,
                                   mem__cold_tier=True)
        assert not p.mem.cold_tier
        assert any("cold_tier" in n for n in p.resolution_notes)

    def test_mem_knobs_without_cold_tier_resolve_to_defaults(self):
        with pytest.warns(PlanResolutionWarning, match="warm_batch"):
            p = ServePlan().evolve(mem__warm_batch=64)
        assert p.mem == MemPlan()

    def test_resolution_idempotent_through_round_trip(self):
        import warnings as w
        with w.catch_warnings():
            w.simplefilter("ignore", PlanResolutionWarning)
            p = ServePlan().evolve(cache__cache_user_reps=False,
                                   mem__cold_tier=True,
                                   mem__promote_touches=9)
        # the resolved plan serializes clean: no warning on reload
        with w.catch_warnings():
            w.simplefilter("error", PlanResolutionWarning)
            p2 = ServePlan.from_json(p.to_json())
        assert p2 == p


# -- RepWarmer ---------------------------------------------------------------
class TestRepWarmer:
    def test_memoizes_shared_feed_objects_per_chunk(self):
        calls = []

        def s1(params, feeds):
            calls.append(1)
            return {"a": feeds["x"] * params}

        cold = ColdRepStore(1 << 20)
        w = RepWarmer(s1, cold, batch=3)
        shared = {"x": np.full((1, 8), 2.0, np.float32)}
        n = w.warm([(u, 0, shared) for u in range(7)], 3.0)
        assert n == 7 and len(cold) == 7
        # 7 users / batch 3 = 3 chunks, one launch per distinct feeds
        # object per chunk
        assert len(calls) == 3
        assert np.allclose(cold.get((4, 0))["a"], 6.0)

    def test_distinct_feeds_each_launch(self):
        def s1(params, feeds):
            return {"a": feeds["x"] + params}

        cold = ColdRepStore(1 << 20)
        w = RepWarmer(s1, cold, batch=8)
        items = [(u, 0, {"x": np.full((1, 4), float(u), np.float32)})
                 for u in range(5)]
        w.warm(items, 10.0)
        assert w.stage1_launches == 5
        for u in range(5):
            assert np.allclose(cold.get((u, 0))["a"], u + 10.0)
