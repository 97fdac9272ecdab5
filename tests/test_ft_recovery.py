"""Self-healing recovery matrix for the fault-injection framework.

The PR's contracts, smallest-scope first:

* fault specs parse/validate deterministically and the ``FaultPlan``
  section rejects or resolves malformed configs at construction;
* the ``FaultInjector`` fires the SAME pokes every run (seeded, counted,
  disarmed pokes advance nothing);
* the ``CircuitBreaker`` walks closed -> open -> half_open -> closed on
  an injectable clock, and a half-open failure re-opens it;
* the batcher's retry path recovers transient failures, respects the
  remaining deadline budget, and resolves exhausted retries with a typed
  ``RetryExhausted`` — never a hang;
* a crashed dispatch loop is respawned on the same thread and every
  future it was holding resolves typed;
* device-tier quarantine rebuilds lazily and stays bit-identical;
  detected corruption is never served.
"""
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.ft import (CORRUPT, FaultInjector, FaultSpec, HeartbeatMonitor,
                      parse_fault_spec, plan_elastic_remesh)
from repro.ft.failures import HedgePolicy
from repro.ft.recovery import CLOSED, HALF_OPEN, OPEN, CircuitBreaker, \
    RetryPolicy
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.serve import (AdmissionError, BatcherClosedError,
                         CircuitOpenError, CoalescingBatcher, FaultInjected,
                         PlanError, PlanResolutionWarning, RetryExhausted,
                         ServePlan, ServeRequest, ServeResult, ServingEngine,
                         WorkerCrashedError)


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


# ---------------------------------------------------------------------------
# Fault specs + FaultPlan validation
# ---------------------------------------------------------------------------

class TestFaultSpecs:
    def test_parse_roundtrip(self):
        s = parse_fault_spec("stage2_dispatch:error:after=10,count=3")
        assert s == FaultSpec(site="stage2_dispatch", kind="error",
                              after=10, count=3)
        assert parse_fault_spec(s.describe()) == s

    def test_delay_param(self):
        s = parse_fault_spec("transfer_copy:delay:delay_ms=25")
        assert s.kind == "delay" and s.delay_ms == 25.0

    @pytest.mark.parametrize("bad", [
        "nope:error",                     # unknown site
        "stage1:explode",                 # unknown kind
        "stage1:error:p=0",               # p outside (0, 1]
        "stage1:error:count=0",           # count < 1
        "stage1:error:after=-1",          # negative after
        "stage1:error:delay_ms=5",        # delay_ms on non-delay kind
        "stage1:error:count",             # malformed k=v
        "stage1:error:zap=1",             # unknown param
        "",                               # empty
    ])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)

    def test_plan_rejects_bad_specs_and_knobs(self):
        with pytest.raises(PlanError, match="ft.sites"):
            ServePlan(ft={"inject": True, "sites": ["stage1:explode"]})
        with pytest.raises(PlanError):
            ServePlan(ft={"retries": -1})
        with pytest.raises(PlanError):
            ServePlan(ft={"retry_jitter": 1.5})
        with pytest.raises(PlanError):
            ServePlan(ft={"breaker_probes": 0})

    def test_plan_drop_and_warn_sites_without_inject(self):
        with pytest.warns(PlanResolutionWarning, match="inject"):
            p = ServePlan(ft={"sites": ["stage1:error"]})
        assert p.ft.sites == () and not p.ft.inject

    def test_plan_json_roundtrip_with_ft(self):
        p = _plan(ft__inject=True, ft__seed=7,
                  ft__sites=("slot_write:error:count=2",),
                  ft__retries=3, ft__breaker_failures=2)
        rt = ServePlan.from_json(p.to_json())
        assert rt == p and rt.ft.sites == ("slot_write:error:count=2",)


class TestFaultInjector:
    def test_count_after_and_determinism(self):
        def fires(seed):
            inj = FaultInjector(("stage1:error:after=2,count=2",), seed=seed)
            out = []
            for i in range(8):
                try:
                    inj.poke("stage1")
                    out.append(False)
                except FaultInjected as e:
                    assert e.site == "stage1"
                    out.append(True)
            return out
        assert fires(0) == [False, False, True, True,
                            False, False, False, False]
        assert fires(0) == fires(0)

    def test_probabilistic_streams_are_seed_stable(self):
        def stream(seed):
            inj = FaultInjector(("pack:corrupt:p=0.5",), seed=seed)
            return [inj.poke("pack") is CORRUPT for _ in range(64)]
        assert stream(3) == stream(3)
        assert stream(3) != stream(4)
        assert any(stream(3)) and not all(stream(3))

    def test_disarmed_pokes_advance_nothing(self):
        inj = FaultInjector(("stage1:error:count=1",))
        inj.set_armed(False)
        for _ in range(5):
            assert inj.poke("stage1") is None
        inj.set_armed(True)
        with pytest.raises(FaultInjected):
            inj.poke("stage1")            # warmup did not consume the count
        assert inj.stats()["total_fired"] == 1

    def test_unknown_site_is_noop(self):
        inj = FaultInjector(("stage1:error",))
        assert inj.poke("collect") is None


# ---------------------------------------------------------------------------
# CircuitBreaker + RetryPolicy units
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def _clocked(self, **kw):
        t = [0.0]
        br = CircuitBreaker(clock=lambda: t[0], **kw)
        return br, t

    def test_full_walk(self):
        seen = []
        t = [0.0]
        br = CircuitBreaker(failures=2, cooldown_ms=100.0, probes=2,
                            clock=lambda: t[0],
                            on_transition=lambda a, b: seen.append((a, b)))
        assert br.state == CLOSED and br.allow()
        br.record_failure()
        assert br.state == CLOSED          # 1 < threshold
        br.record_failure()
        assert br.state == OPEN and not br.allow()
        t[0] = 0.05
        assert not br.allow()              # cooldown not elapsed
        t[0] = 0.11
        assert br.allow() and br.state == HALF_OPEN
        br.record_success()
        assert br.state == HALF_OPEN       # 1 of 2 probes
        br.record_success()
        assert br.state == CLOSED
        assert seen == [(CLOSED, OPEN), (OPEN, HALF_OPEN),
                        (HALF_OPEN, CLOSED)]
        st = br.stats()
        assert st["opens"] == 1 and st["closes"] == 1

    def test_half_open_failure_reopens(self):
        br, t = self._clocked(failures=1, cooldown_ms=50.0)
        br.record_failure()
        t[0] = 0.06
        assert br.allow() and br.state == HALF_OPEN
        br.record_failure()
        assert br.state == OPEN
        t[0] = 0.08                        # cooldown restarted at reopen
        assert not br.allow()
        t[0] = 0.12
        assert br.allow() and br.state == HALF_OPEN

    def test_success_resets_consecutive_failures(self):
        br, _ = self._clocked(failures=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == CLOSED          # never 2 consecutive

    def test_call_raises_typed_while_open(self):
        br, t = self._clocked(failures=1, cooldown_ms=1000.0)
        with pytest.raises(RuntimeError):
            br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")))
        assert br.state == OPEN
        with pytest.raises(CircuitOpenError):
            br.call(lambda: 1)
        t[0] = 2.0
        assert br.call(lambda: 41) == 41 and br.state == CLOSED

    def test_ctor_validation(self):
        for kw in (dict(failures=0), dict(cooldown_ms=-1), dict(probes=0)):
            with pytest.raises(ValueError):
                CircuitBreaker(**kw)


class TestRetryPolicy:
    def test_backoff_doubles_without_jitter(self):
        p = RetryPolicy(retries=3, backoff_ms=2.0, jitter=0.0)
        assert [p.backoff_s(a) for a in range(3)] == [0.002, 0.004, 0.008]

    def test_jitter_bounded(self):
        import random
        p = RetryPolicy(retries=1, backoff_ms=10.0, jitter=0.5)
        rng = random.Random(0)
        for a in range(6):
            base = 10.0 * 2 ** a / 1e3
            assert base <= p.backoff_s(a, rng=rng) <= base * 1.5


# ---------------------------------------------------------------------------
# Batcher: retry, deadline budget, worker supervision
# ---------------------------------------------------------------------------

class _FlakyEngine:
    """Spy engine: fails the first ``fail_calls`` score_coalesced calls,
    then succeeds; records call sizes."""
    max_batch = 1 << 30
    _multiproc = False

    def __init__(self, fail_calls=0, exc=None):
        self.fail_calls = fail_calls
        self.exc = exc or FaultInjected("boom", site="stage2_dispatch")
        self.calls: list[int] = []

    def new_group_id(self):
        return None

    def score_coalesced(self, reqs, gid=None):
        self.calls.append(len(reqs))
        if len(self.calls) <= self.fail_calls:
            raise self.exc
        return [ServeResult(scores=np.full((self._rows(r), 1),
                                           float(r.user_id)),
                            latency_ms=0.0, n_batches=1,
                            user_cache_hit=False) for r in reqs]

    @staticmethod
    def _rows(r):
        return next(iter(r.candidate_feeds.values())).shape[0]


def _tiny_req(uid, n=8):
    return ServeRequest(uid, {}, {"x": np.zeros((n, 2), np.float32)})


class TestBatcherRetry:
    def test_retry_recovers_transient_failure(self):
        eng = _FlakyEngine(fail_calls=1)
        with CoalescingBatcher(eng, linger_ms=0.5, continuous=False,
                               retries=2, retry_backoff_ms=0.1,
                               retry_jitter=0.0) as b:
            res = b.submit(_tiny_req(7)).result(timeout=10)
        assert float(res.scores[0, 0]) == 7.0
        assert b.retries_attempted == 1 and b.retries_exhausted == 0
        assert eng.calls == [1, 1]         # group, then the retry

    def test_retry_exhausted_is_typed_with_cause(self):
        eng = _FlakyEngine(fail_calls=100)
        with CoalescingBatcher(eng, linger_ms=0.5, continuous=False,
                               retries=2, retry_backoff_ms=0.1,
                               retry_jitter=0.0) as b:
            fut = b.submit(_tiny_req(1))
            with pytest.raises(RetryExhausted) as ei:
                fut.result(timeout=10)
        assert ei.value.attempts == 2
        assert isinstance(ei.value.__cause__, FaultInjected)
        assert b.retries_exhausted == 1

    def test_retry_respects_deadline_budget(self):
        eng = _FlakyEngine(fail_calls=100)
        with CoalescingBatcher(eng, linger_ms=0.0, continuous=False,
                               retries=5, retry_backoff_ms=200.0,
                               retry_jitter=0.0) as b:
            t0 = time.perf_counter()
            fut = b.submit(_tiny_req(1), deadline_ms=20.0)
            with pytest.raises(RetryExhausted) as ei:
                fut.result(timeout=10)
            elapsed = time.perf_counter() - t0
        # the 200 ms backoff exceeded the 20 ms budget: zero sleeps taken
        assert ei.value.attempts == 0
        assert elapsed < 1.0
        assert isinstance(ei.value.__cause__, FaultInjected)

    def test_zero_retries_propagates_original_error(self):
        eng = _FlakyEngine(fail_calls=100)
        with CoalescingBatcher(eng, linger_ms=0.5,
                               continuous=False) as b:
            fut = b.submit(_tiny_req(1))
            with pytest.raises(FaultInjected):
                fut.result(timeout=10)

    def test_typed_refusals_never_retried(self):
        eng = _FlakyEngine(fail_calls=100,
                           exc=AdmissionError("no", slo="best_effort",
                                              queue_depth=0))
        with CoalescingBatcher(eng, linger_ms=0.5, continuous=False,
                               retries=3, retry_backoff_ms=0.1) as b:
            fut = b.submit(_tiny_req(1))
            with pytest.raises(AdmissionError):
                fut.result(timeout=10)
        assert b.retries_attempted == 0

    def test_from_plan_wires_ft_retry_knobs(self):
        eng = _FlakyEngine()
        plan = _plan(ft__inject=True, ft__sites=("stage1:error:count=1",),
                     ft__retries=4, ft__retry_backoff_ms=3.0)
        b = CoalescingBatcher.from_plan(eng, plan.batch, plan.ft,
                                        auto_start=False)
        assert b.retries == 4
        assert b._retry_policy.backoff_ms == 3.0


class TestWorkerSupervision:
    def _crashy_engine(self, count=1):
        eng = _FlakyEngine()
        eng.fault_injector = FaultInjector(
            (f"worker_loop:error:count={count}",))
        return eng

    def test_respawn_resolves_crash_victims_via_retry(self):
        eng = self._crashy_engine()
        with CoalescingBatcher(eng, linger_ms=0.5, continuous=False,
                               retries=2, retry_backoff_ms=0.1,
                               retry_jitter=0.0) as b:
            r1 = b.submit(_tiny_req(3)).result(timeout=10)
            # the loop crashed forming the first group; the victim was
            # re-scored individually and the loop respawned for the rest
            r2 = b.submit(_tiny_req(4)).result(timeout=10)
        assert float(r1.scores[0, 0]) == 3.0
        assert float(r2.scores[0, 0]) == 4.0
        assert b.worker_crashes == 1 and b.worker_respawns == 1

    def test_crash_without_retries_fails_typed_never_hangs(self):
        eng = self._crashy_engine()
        with CoalescingBatcher(eng, linger_ms=0.5,
                               continuous=False) as b:
            fut = b.submit(_tiny_req(3))
            with pytest.raises(WorkerCrashedError) as ei:
                fut.result(timeout=10)
            assert isinstance(ei.value.__cause__, FaultInjected)
            # the respawned loop serves subsequent traffic normally
            r2 = b.submit(_tiny_req(4)).result(timeout=10)
        assert float(r2.scores[0, 0]) == 4.0
        assert b.worker_crashes == 1 and b.worker_respawns == 1

    def test_close_after_crash_strands_nothing(self):
        eng = self._crashy_engine(count=2)
        b = CoalescingBatcher(eng, linger_ms=0.5, continuous=False,
                              retries=1, retry_backoff_ms=0.1,
                              retry_jitter=0.0)
        futs = [b.submit(_tiny_req(i)) for i in range(6)]
        b.close()
        for f in futs:
            assert f.done()                # resolved, one way or another
            if f.exception() is not None:
                assert isinstance(f.exception(),
                                  (WorkerCrashedError, BatcherClosedError))


# ---------------------------------------------------------------------------
# Engine: quarantine, breaker, corruption (real two-stage fixture)
# ---------------------------------------------------------------------------

class TestEngineSelfHealing:
    def _reqs(self, graph, user_in, uids, n=12):
        return [_request(graph, user_in, u, n, seed=u) for u in uids]

    def test_quarantine_then_rebuild_is_bit_identical(self, paper):
        graph, params, user_in = paper
        reqs = self._reqs(graph, user_in, [0, 1, 2, 0, 1, 2])
        ref_eng = ServingEngine(graph, params, plan=_plan())
        refs = [r.scores for r in [ref_eng.score(q) for q in reqs]]

        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("slot_write:error:count=1",)))
        out = [eng.score(q).scores for q in reqs]
        # the faulted write quarantined the tier; the pack fell back to
        # re-stacking, later calls rebuilt the table — scores never moved
        for a, b in zip(out, refs):
            assert np.array_equal(a, b)
        assert eng.device_store.stats()["quarantines"] == 1
        assert eng.device_store.stats()["resident"] > 0   # rebuilt lazily

    def test_breaker_open_fallback_halfopen_close(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("slot_write:error:count=3",),
            ft__breaker_failures=2, ft__breaker_cooldown_ms=40.0,
            ft__breaker_probes=1))
        ref_eng = ServingEngine(graph, params, plan=_plan())
        transitions = []
        orig = eng.breaker._on_transition
        eng.breaker._on_transition = \
            lambda a, b: (transitions.append((a, b)), orig(a, b))

        def score(uid):
            r = _request(graph, user_in, uid, 12, seed=uid)
            got = eng.score(r).scores
            assert np.array_equal(got, ref_eng.score(r).scores)

        score(0)                           # fault 1 -> quarantine
        score(1)                           # fault 2 -> quarantine -> OPEN
        assert eng.breaker.state == OPEN
        fb0 = eng.fallback_packs
        score(2)                           # while open: re-stack fallback
        assert eng.fallback_packs > fb0
        time.sleep(0.06)                   # past the cooldown
        score(3)                           # half-open probe: fault 3 reopens
        assert eng.breaker.state == OPEN
        time.sleep(0.06)
        score(4)                           # clean probe -> CLOSED
        assert eng.breaker.state == CLOSED
        assert (CLOSED, OPEN) in transitions
        assert (OPEN, HALF_OPEN) in transitions
        assert (HALF_OPEN, CLOSED) in transitions
        assert eng.breaker.stats()["opens"] == 2

    def test_corruption_detected_never_served(self, paper):
        graph, params, user_in = paper
        req = _request(graph, user_in, 0, 12, seed=0)
        ref = ServingEngine(graph, params,
                            plan=_plan()).score(req).scores
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("collect:corrupt:count=1",)))
        with pytest.raises(FaultInjected, match="corrupt"):
            eng.score_coalesced([req])
        assert eng.corruptions_detected == 1
        # the retry (here: a plain re-score) recomputes clean rows
        assert np.array_equal(eng.score_coalesced([req])[0].scores, ref)

    def test_corrupt_slot_write_detected_and_requarantined(self, paper):
        graph, params, user_in = paper
        req = _request(graph, user_in, 5, 12, seed=5)
        ref = ServingEngine(graph, params,
                            plan=_plan()).score(req).scores
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("slot_write:corrupt:count=1",)))
        # the poisoned device row NaNs the scores; collect detects it,
        # quarantines the tier, and raises rather than serving garbage
        with pytest.raises(FaultInjected):
            eng.score_coalesced([req])
        assert eng.corruptions_detected == 1
        assert eng.device_store.stats()["quarantines"] == 1
        assert np.array_equal(eng.score_coalesced([req])[0].scores, ref)

    def test_retry_through_batcher_stays_bit_identical(self, paper):
        graph, params, user_in = paper
        reqs = self._reqs(graph, user_in, [0, 1, 2, 3])
        ref_eng = ServingEngine(graph, params, plan=_plan())
        refs = [ref_eng.score(q).scores for q in reqs]
        eng = ServingEngine(graph, params, plan=_plan(
            ft__inject=True, ft__sites=("stage2_dispatch:error:count=2",),
            ft__retries=3, ft__retry_backoff_ms=0.5))
        plan = _plan(ft__retries=3, ft__retry_backoff_ms=0.5,
                     ft__retry_jitter=0.0)
        with CoalescingBatcher.from_plan(eng, plan.batch, plan.ft) as b:
            futs = [b.submit(q) for q in reqs]
            out = [f.result(timeout=60).scores for f in futs]
        for a, b_ in zip(out, refs):
            assert np.array_equal(a, b_)


# ---------------------------------------------------------------------------
# Satellites: heartbeat resurrection, remesh edges, hedge policy
# ---------------------------------------------------------------------------

class TestHeartbeatSticky:
    def test_removed_worker_stays_removed_on_stray_beat(self):
        t = [0.0]
        hb = HeartbeatMonitor(["a", "b"], timeout=1.0, clock=lambda: t[0])
        hb.remove("a")
        hb.heartbeat("a")                  # stray beat from the removed
        assert "a" not in hb.alive() and "a" not in hb.dead()
        t[0] = 2.0
        assert hb.dead() == ["b"] and "a" not in hb.dead()

    def test_explicit_add_rejoins(self):
        t = [0.0]
        hb = HeartbeatMonitor(["a"], timeout=1.0, clock=lambda: t[0])
        hb.remove("a")
        t[0] = 5.0
        hb.add("a")                        # explicit rejoin, fresh clock
        assert hb.alive() == ["a"]
        hb.heartbeat("a")                  # beats register again
        t[0] = 5.5
        assert hb.alive() == ["a"]


class TestElasticRemeshEdges:
    def test_non_pow2_survivors_round_down(self):
        p = plan_elastic_remesh((4, 2), ("data", "model"), 6)
        assert p.new_shape == (2, 2)       # dp budget 3 -> largest pow2 2
        assert p.dropped_devices == 2
        assert p.global_batch_scale == 0.5

    def test_pod_collapses_into_data(self):
        p = plan_elastic_remesh((2, 2, 2), ("pod", "data", "model"), 4)
        assert p.new_shape == (1, 2, 2)
        assert p.global_batch_scale == 0.5

    def test_tp_unpreservable_raises(self):
        with pytest.raises(ValueError, match="TP"):
            plan_elastic_remesh((2, 4), ("data", "model"), 3)


class TestHedgingPool:
    def test_policy_concurrent_observe_and_read(self):
        pol = HedgePolicy(window=64, min_hedge_ms=1.0)
        stop = threading.Event()
        errs = []

        def writer():
            i = 0
            while not stop.is_set():
                pol.observe(float(i % 37))
                i += 1

        def reader():
            try:
                while not stop.is_set():
                    pol.hedge_deadline_ms()
            except Exception as e:         # pragma: no cover - the bug
                errs.append(e)

        ts = [threading.Thread(target=f) for f in (writer, reader, reader)]
        for th in ts:
            th.start()
        time.sleep(0.2)
        stop.set()
        for th in ts:
            th.join()
        assert not errs


class TestErrorTaxonomy:
    def test_batcher_reexports_are_canonical(self):
        import repro.serve.batcher as B
        import repro.serve.errors as E
        assert B.AdmissionError is E.AdmissionError
        assert B.BatcherClosedError is E.BatcherClosedError
        from repro.serve import AdmissionError as SA
        assert SA is E.AdmissionError

    def test_hierarchy(self):
        from repro.serve.errors import ServeError
        for ex in (AdmissionError, BatcherClosedError, FaultInjected,
                   RetryExhausted, CircuitOpenError, WorkerCrashedError):
            assert issubclass(ex, ServeError)
            assert issubclass(ex, RuntimeError)

    def test_future_from_stdlib_still_typed(self):
        # the taxonomy is stdlib-importable: no jax needed to CATCH
        fut = Future()
        fut.set_exception(RetryExhausted("x", attempts=2))
        assert isinstance(fut.exception(), RetryExhausted)
        assert fut.exception().attempts == 2
