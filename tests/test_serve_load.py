"""Continuous dispatch loop + SLO admission control + close-drain.

Three contracts of the PR-7 serving loop:

* the continuous loop changes WHEN groups launch, never WHAT they
  compute — scores stay bit-identical to per-request scoring and to the
  lockstep batcher, including across the copy-on-write generation forks
  cold users force mid-stream;
* admission control sheds/degrades best_effort work before deadline work
  under overload, and a shed future fails FAST with a typed
  ``AdmissionError`` — it never hangs;
* ``close()`` drains: every admitted request is scored (or failed with
  the scoring error), never silently abandoned.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.serve import (AdmissionError, BatcherClosedError,
                         CoalescingBatcher, FaultInjected, RankingService,
                         ServePlan, ServeRequest, ServeResult, ServingEngine)


@pytest.fixture(scope="module")
def paper():
    graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.05))
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    return graph, params, user_in


def _request(graph, user_in, uid, n, seed, version=0):
    feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(seed))
    return ServeRequest(
        user_id=uid,
        user_feeds={k: v for k, v in feeds.items() if k in user_in},
        candidate_feeds={k: v for k, v in feeds.items() if k not in user_in},
        feature_version=version)


def _plan(**over):
    base = dict(batch__max_batch=128, cache__device_resident=True,
                cache__device_slots=8)
    base.update(over)
    return ServePlan().evolve(**base)


class TestContinuousLoopIdentity:
    """Bit-identity of the continuous loop vs per-request and lockstep."""

    def _mixed_stream(self, graph, user_in):
        # repeat users (all-resident overlap path) interleaved with cold
        # users (each forces a generation fork before its table write)
        reqs = []
        for i in range(12):
            uid = i % 3 if i % 2 == 0 else 100 + i    # hot trio + cold tail
            reqs.append(_request(graph, user_in, uid, 10 + (i % 4) * 3,
                                 seed=i))
        return reqs

    def test_continuous_matches_per_request(self, paper):
        graph, params, user_in = paper
        reqs = self._mixed_stream(graph, user_in)
        ref_eng = ServingEngine(graph, params, plan=_plan())
        ref = [ref_eng.score(r) for r in reqs]

        eng = ServingEngine(graph, params, plan=_plan())
        with CoalescingBatcher(eng, linger_ms=20.0, max_coalesce=4,
                               continuous=True, max_inflight=2) as b:
            futs = [b.submit(r) for r in reqs]
            out = [f.result(timeout=120) for f in futs]
        for p, c in zip(ref, out):
            assert np.array_equal(p.scores, c.scores)
        assert b.batches >= 1 and b.requests == len(reqs)

    def test_continuous_matches_lockstep(self, paper):
        graph, params, user_in = paper
        reqs = self._mixed_stream(graph, user_in)
        outs = {}
        for continuous in (False, True):
            eng = ServingEngine(graph, params, plan=_plan())
            with CoalescingBatcher(eng, linger_ms=20.0, max_coalesce=4,
                                   continuous=continuous) as b:
                futs = [b.submit(r) for r in reqs]
                outs[continuous] = [f.result(timeout=120) for f in futs]
        for lock, cont in zip(outs[False], outs[True]):
            assert np.array_equal(lock.scores, cont.scores)

    def test_two_phase_api_overlap_and_fork(self, paper):
        """Direct engine contract: an all-resident call overlaps freely;
        a call needing a table write forks the table generation (copy-on-
        write) instead of draining — both stay bit-identical."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan())
        ref_eng = ServingEngine(graph, params, plan=_plan())

        warm = [_request(graph, user_in, u, 12, seed=u) for u in (0, 1)]
        eng.score_coalesced(warm)               # users 0, 1 now resident
        ref_eng.score_coalesced(warm)

        again = [_request(graph, user_in, u, 9, seed=10 + u) for u in (0, 1)]
        cold = [_request(graph, user_in, 7, 9, seed=20)]    # needs a write
        h1 = eng.begin_coalesced(again)
        assert eng.pipeline_forks == 0
        h2 = eng.begin_coalesced(cold)          # forks the generation —
        assert eng.pipeline_forks == 1          # h1 stays in flight
        assert eng.device_store.stats()["forks"] == 1
        r2 = eng.collect(h2)                    # out-of-order collect is fine
        r1 = eng.collect(h1)
        for got, ref in zip(r1 + r2,
                            ref_eng.score_coalesced(again)
                            + ref_eng.score_coalesced(cold)):
            assert np.array_equal(got.scores, ref.scores)

        with pytest.raises(RuntimeError, match="not in flight"):
            eng.collect(h1)                     # each handle collects once

    def test_overlap_launch_all_resident(self, paper):
        """Two all-resident calls in flight at once never fork (hits read
        the shared table generation — no copy, no drain)."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan())
        eng.score_coalesced([_request(graph, user_in, u, 8, seed=u)
                             for u in (0, 1)])
        h1 = eng.begin_coalesced([_request(graph, user_in, 0, 8, seed=5)])
        h2 = eng.begin_coalesced([_request(graph, user_in, 1, 8, seed=6)])
        eng.collect(h1)
        eng.collect(h2)
        assert eng.pipeline_forks == 0

    def test_overlapped_transfer_buffers_are_private(self, paper):
        """Regression: a pack's host->device transfer copy executes
        asynchronously on the device stream, behind every in-flight
        executable — so a later same-bucket pack must never reuse the
        earlier pack's host buffer. A shared per-bucket staging buffer
        let the second call's refill win that race and silently score
        the first call's request against the second call's candidate
        rows (re-stacking path; the device tier masks nothing here,
        candidates ride the same buffers)."""
        graph, params, user_in = paper
        plan = ServePlan().evolve(batch__max_batch=1024,
                                  cache__device_resident=False)
        eng = ServingEngine(graph, params, plan=plan)
        # big fills 6 full packs; victim lands alone in a 7th pack whose
        # transfer copy queues behind all 6 executables — the widest
        # possible race window for attacker's same-bucket refill
        big = _request(graph, user_in, 0, 6 * 1024, seed=0)
        victim = _request(graph, user_in, 1, 1000, seed=1)    # bucket 1024
        attacker = _request(graph, user_in, 2, 900, seed=2)   # bucket 1024
        ref = [eng.score(r) for r in (big, victim, attacker)]
        for _ in range(3):
            h1 = eng.begin_coalesced([big, victim])
            h2 = eng.begin_coalesced([attacker])  # same-bucket refill while
            out = eng.collect(h1) + eng.collect(h2)   # victim copy pends
            for got, want in zip(out, ref):
                assert np.array_equal(got.scores, want.scores)

    def test_loop_profiler_phases(self, paper):
        """The loop's queue_idle phase surfaces in the profile."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan())
        with CoalescingBatcher(eng, linger_ms=0.0, continuous=True) as b:
            futs = [b.submit(_request(graph, user_in, u % 2, 8, seed=u))
                    for u in range(8)]
            for f in futs:
                f.result(timeout=120)
            time.sleep(0.12)                    # an idle tick or two
        snap = eng.profiler.snapshot()
        assert snap["queue_idle"]["calls"] >= 1
        assert set(snap) == {"stage1", "pack", "dispatch", "device",
                             "unpack", "queue_idle"}


def _set_ids(bufsets):
    """ids of every host buffer in a handle's staging sets."""
    return [id(b) for uidx, cand in bufsets
            for b in (uidx, *cand.values())]


def _free_ids(eng):
    return [id(b) for free in eng._pack_free.values()
            for uidx, cand in free for b in (uidx, *cand.values())]


class TestPackBufferPool:
    """Pack staging sets are private to their pack until it is collected,
    then recycled; error paths drop them; the free lists stay bounded."""

    def test_serial_same_bucket_calls_reuse_one_set(self, paper):
        graph, params, user_in = paper
        reqs = [_request(graph, user_in, u, 90 + u, seed=u)
                for u in range(3)]                  # all bucket 128
        eng = ServingEngine(graph, params, plan=_plan())
        out = []
        for i, req in enumerate(reqs):
            out.append(eng.score(req))
            assert eng.pack_buffers_allocated == 1
            assert eng.pack_buffers_reused == i
        snap = eng.metrics.snapshot()
        assert snap["pack_buffers_allocated"] == 1
        assert snap["pack_buffers_reused"] == len(reqs) - 1
        for req, got in zip(reqs, out):
            fresh = ServingEngine(graph, params, plan=_plan())
            assert np.array_equal(fresh.score(req).scores, got.scores)
            assert fresh.pack_buffers_reused == 0

        svc = RankingService(_plan())
        svc.register("ranking", graph=graph, params=params)
        for req in reqs:
            svc.score("ranking", req)
        st = svc.stats()["scenarios"]["ranking"]
        assert st["pack_buffers_allocated"] == 1
        assert st["pack_buffers_reused"] == len(reqs) - 1
        svc.close()

    def test_overlapped_packs_never_share_a_staging_set(self, paper):
        graph, params, user_in = paper
        plan = ServePlan().evolve(batch__max_batch=1024,
                                  cache__device_resident=False)
        eng = ServingEngine(graph, params, plan=plan)
        big = _request(graph, user_in, 0, 3 * 1024, seed=0)
        victim = _request(graph, user_in, 1, 1000, seed=1)    # bucket 1024
        attacker = _request(graph, user_in, 2, 900, seed=2)   # bucket 1024
        ref = [eng.score(r) for r in (big, victim, attacker)]
        for _ in range(3):
            h1 = eng.begin_coalesced([big, victim])
            h2 = eng.begin_coalesced([attacker])
            held = _set_ids(h1.bufsets) + _set_ids(h2.bufsets)
            assert len(held) == len(set(held))      # no set held twice
            assert not set(held) & set(_free_ids(eng))
            out = eng.collect(h1) + eng.collect(h2)
            for got, want in zip(out, ref):
                assert np.array_equal(got.scores, want.scores)
        # the serial calls reuse 2 of their 5 packs' sets; the first
        # overlap (5 packs in flight) reuses the 3 sets and allocates 2;
        # the next two overlaps reuse all 5
        assert eng.pack_buffers_allocated == 5
        assert eng.pack_buffers_reused == 2 + 3 + 2 * 5

    @pytest.mark.parametrize("site", ["collect:error", "collect:corrupt",
                                      "stage2_dispatch:error"])
    def test_failed_group_drops_its_sets(self, paper, site):
        graph, params, user_in = paper
        req = _request(graph, user_in, 0, 100, seed=0)
        want = ServingEngine(graph, params, plan=_plan()).score(req).scores
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=(f"{site}:after=1,count=1",)))
        assert np.array_equal(eng.score_coalesced([req])[0].scores, want)
        pooled = set(_free_ids(eng))
        h = eng.begin_coalesced([req]) if site.startswith("collect") \
            else None
        with pytest.raises(FaultInjected):
            if h is None:
                eng.begin_coalesced([req])
            else:
                eng.collect(h)
        assert eng.pack_buffers_reused == 1         # the failed pack's set
        assert eng._pack_free[128] == []            # ... is not returned
        for _ in range(2):
            assert np.array_equal(eng.score_coalesced([req])[0].scores,
                                  want)
        assert eng.pack_buffers_allocated == 2
        assert not pooled & set(_free_ids(eng))

    def test_poisoned_set_is_dropped_and_refill_rewrites_every_row(
            self, paper):
        graph, params, user_in = paper
        req = _request(graph, user_in, 0, 100, seed=0)
        want = ServingEngine(graph, params, plan=_plan()).score(req).scores
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("transfer_copy:corrupt:after=1,"
                                        "count=1",)))
        eng.score_coalesced([req])
        poisoned = set(_free_ids(eng))
        with pytest.raises(FaultInjected, match="corrupt"):
            eng.score_coalesced([req])              # NaN-poisoned transfer
        assert eng.corruptions_detected == 1
        got = eng.score_coalesced([req])[0].scores
        assert np.isfinite(got).all() and np.array_equal(got, want)
        assert not poisoned & set(_free_ids(eng))
        # a set that comes back NaN-filled is rewritten in full by its
        # next fill, padding rows included
        for cand in (c for _, c in eng._pack_free[128]):
            for buf in cand.values():
                buf.fill(np.nan)
        reused = eng.pack_buffers_reused
        got = eng.score_coalesced([req])[0].scores
        assert eng.pack_buffers_reused == reused + 1
        assert np.isfinite(got).all() and np.array_equal(got, want)

    def test_free_lists_stay_under_their_cap(self, paper, monkeypatch):
        from repro.serve import engine as engine_mod
        cap = engine_mod._PACK_SETS_PER_BUCKET
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan())
        # more same-bucket packs in flight than the cap: the surplus is
        # dropped at collect
        hs = [eng.begin_coalesced([_request(graph, user_in, 0, 100,
                                            seed=s)])
              for s in range(cap + 2)]
        for h in hs:
            eng.collect(h)
        assert eng.pack_buffers_allocated == cap + 2
        assert len(eng._pack_free[128]) == cap

        longest = []
        collect_body = eng._collect_body

        def watched(handle):
            out = collect_body(handle)
            longest.append(max(map(len, eng._pack_free.values())))
            return out

        monkeypatch.setattr(eng, "_collect_body", watched)
        reqs = [_request(graph, user_in, i % 5, 20 + 37 * (i % 7), seed=i)
                for i in range(60)]             # buckets 128 to 256
        with CoalescingBatcher(eng, linger_ms=1.0, max_coalesce=3,
                               continuous=True, max_inflight=4) as b:
            for f in [b.submit(r) for r in reqs]:
                f.result(timeout=120)
        assert len(longest) == b.batches and max(longest) <= cap
        assert eng.pack_buffers_reused > eng.pack_buffers_allocated


class _GatedResultEngine:
    """Engine stand-in: the FIRST group blocks on a gate so submissions
    pile up behind it; every request's rows and SLO-visible shape are
    recorded; results are real ServeResult objects."""
    max_batch = 1 << 30

    def __init__(self):
        self.scored: list[ServeRequest] = []
        self.gate = threading.Event()
        self.first_group = threading.Event()

    def _rows(self, req):
        return next(iter(req.candidate_feeds.values())).shape[0]

    def new_group_id(self):
        return None

    def score_coalesced(self, reqs, gid=None):
        hold = not self.first_group.is_set()
        self.first_group.set()
        self.scored.extend(reqs)
        if hold:
            self.gate.wait(timeout=30)
        return [ServeResult(scores=np.zeros((self._rows(r), 1)),
                            latency_ms=0.0, n_batches=1,
                            user_cache_hit=False) for r in reqs]


def _tiny_req(uid, n=8):
    return ServeRequest(uid, {}, {"x": np.zeros((n, 2), np.float32)})


class TestAdmissionControl:
    def _held_batcher(self, **kw):
        spy = _GatedResultEngine()
        b = CoalescingBatcher(spy, linger_ms=0.0, max_coalesce=1,
                              admission=True, **kw)
        blocker = b.submit(_tiny_req(999))
        assert spy.first_group.wait(timeout=30)   # worker now held mid-group
        return spy, b, blocker

    def test_best_effort_shed_fails_fast_and_typed(self):
        spy, b, blocker = self._held_batcher(shed_queue_depth=3)
        try:
            admitted = [b.submit(_tiny_req(u)) for u in range(3)]
            t0 = time.perf_counter()
            shed = b.submit(_tiny_req(50))
            waited = time.perf_counter() - t0
            assert shed.done()                    # failed at submit: no hang
            assert waited < 1.0
            with pytest.raises(AdmissionError) as ei:
                shed.result(timeout=1)
            assert ei.value.slo == "best_effort"
            assert ei.value.queue_depth >= 3
            spy.gate.set()
            for f in [blocker] + admitted:
                f.result(timeout=30)
        finally:
            spy.gate.set()
            b.close()
        assert b.shed_requests == 1 and b.shed_best_effort == 1
        assert b.shed_deadline == 0
        # shed user 50 never reached the engine
        assert 50 not in [r.user_id for r in spy.scored]

    def test_deadline_never_shed_while_best_effort_is(self):
        """The satellite contract: at a depth where best_effort is shed,
        deadline-class submissions are still admitted and scored."""
        spy, b, blocker = self._held_batcher(shed_queue_depth=2)
        try:
            filler = [b.submit(_tiny_req(u)) for u in range(2)]
            for u in (60, 61):                    # depth >= shed threshold
                with pytest.raises(AdmissionError):
                    b.submit(_tiny_req(u)).result(timeout=1)
            dl = [b.submit(_tiny_req(70 + i), slo="deadline")
                  for i in range(3)]
            spy.gate.set()
            for f in [blocker] + filler + dl:
                f.result(timeout=30)              # every admitted one scored
        finally:
            spy.gate.set()
            b.close()
        assert b.shed_best_effort == 2 and b.shed_deadline == 0
        scored = [r.user_id for r in spy.scored]
        assert all(70 + i in scored for i in range(3))

    def test_infeasible_deadline_shed(self):
        spy = _GatedResultEngine()
        spy.gate.set()
        with CoalescingBatcher(spy, linger_ms=0.0, admission=True,
                               deadline_headroom_ms=5.0) as b:
            with pytest.raises(AdmissionError, match="headroom"):
                b.submit(_tiny_req(1), deadline_ms=2.0).result(timeout=1)
            ok = b.submit(_tiny_req(2), deadline_ms=50.0)
            ok.result(timeout=30)
            assert b.shed_deadline == 1

    def test_degrade_truncates_best_effort_only(self):
        spy, b, blocker = self._held_batcher(degrade_queue_depth=1,
                                             degrade_frac=0.5)
        try:
            filler = b.submit(_tiny_req(1))       # depth 1: degrades follow
            deg = b.submit(_tiny_req(2, n=8))
            dl = b.submit(_tiny_req(3, n=8), slo="deadline")
            spy.gate.set()
            res = deg.result(timeout=30)
            assert res.degraded is True
            assert res.scores.shape[0] == 4       # ceil(8 * 0.5)
            assert dl.result(timeout=30).degraded is False
            for f in (blocker, filler):
                f.result(timeout=30)
        finally:
            spy.gate.set()
            b.close()
        assert b.degraded_requests == 1
        rows = {r.user_id: next(iter(r.candidate_feeds.values())).shape[0]
                for r in spy.scored}
        assert rows[2] == 4 and rows[3] == 8      # deadline kept its pool

    def test_admission_off_never_sheds(self):
        spy, b, blocker = self._held_batcher(shed_queue_depth=1)
        b.admission = False                       # thresholds present, off
        try:
            futs = [b.submit(_tiny_req(u)) for u in range(4)]
            spy.gate.set()
            for f in [blocker] + futs:
                f.result(timeout=30)
        finally:
            spy.gate.set()
            b.close()
        assert b.shed_requests == 0

    def test_service_stats_surface_shed_counters(self):
        plan = ServePlan().evolve(batch__admission=True,
                                  batch__shed_queue_depth=64,
                                  batch__deadline_headroom_ms=1.0)
        with RankingService(plan, smoke=True, seed=0) as svc:
            svc.register("din")
            feeds = make_recsys_feeds(svc.source_graph("din"), 6,
                                      jax.random.PRNGKey(1))
            uf, cf = svc.split_feeds("din", feeds)
            svc.score("din", ServeRequest(1, uf, cf))
            with pytest.raises(AdmissionError):
                svc.submit("din", ServeRequest(2, uf, cf),
                           deadline_ms=0.5).result(timeout=1)
            sc = svc.stats()["scenarios"]["din"]
        assert sc["shed_requests"] == 1 and sc["shed_deadline"] == 1
        assert sc["shed_best_effort"] == 0
        assert sc["degraded_requests"] == 0
        assert "pipeline_forks" in sc


class TestCloseDrain:
    def test_close_scores_queued_requests(self, paper):
        """The close() bugfix: queued-but-unclaimed requests are scored
        during the drain, not abandoned — even mid-linger."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan())
        eng.score(_request(graph, user_in, 0, 10, seed=0))   # precompile
        # a huge linger would strand queued items without the drain: the
        # old worker lingered per group even while stopping
        b = CoalescingBatcher(eng, linger_ms=60_000.0, max_coalesce=2)
        futs = [b.submit(_request(graph, user_in, u, 10, seed=u))
                for u in range(6)]
        b.close()                                 # must drain, fast
        for f in futs:
            res = f.result(timeout=1)             # already resolved
            assert res.scores.shape[0] == 10

    def test_close_under_load_leaves_nothing_hanging(self):
        """Close fired while the worker is mid-group: the held group AND
        everything queued behind it still resolve."""
        spy = _GatedResultEngine()
        b = CoalescingBatcher(spy, linger_ms=0.0, max_coalesce=1)
        blocker = b.submit(_tiny_req(0))
        assert spy.first_group.wait(timeout=30)
        futs = [b.submit(_tiny_req(u)) for u in range(1, 8)]
        closer = threading.Thread(target=b.close)
        closer.start()
        time.sleep(0.05)
        spy.gate.set()                            # release the held group
        closer.join(timeout=30)
        assert not closer.is_alive()
        for f in [blocker] + futs:
            assert f.result(timeout=5) is not None
        assert len(spy.scored) == 8

    def test_stranded_future_fails_typed(self):
        """The backstop: items a dead worker never claimed fail with
        BatcherClosedError instead of hanging their waiter."""
        from concurrent.futures import Future

        from repro.serve.batcher import _Item
        spy = _GatedResultEngine()
        spy.gate.set()
        b = CoalescingBatcher(spy, auto_start=False)
        fut = Future()
        b._q.put(_Item(prio=1, seq=b._next_seq(), req=_tiny_req(1), fut=fut,
                       submitted_at=time.perf_counter()))
        b.close()                                 # no worker ever ran
        with pytest.raises(BatcherClosedError):
            fut.result(timeout=1)
