"""The async coalescing serve runtime: cross-user stage-2 batching, bounded
LRU user-rep cache, weight pre-concatenation, and
candidate-axis sharding. Scores from differently shaped executables are
checked against the float32 reference within the stated CPU tolerance
(``repro.serve.reference``); bit-equality is asserted only where XLA:CPU
keeps it.
"""
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.models.recsys import build_din
from repro.serve import (CoalescingBatcher, ServePlan, ServeRequest,
                         ServingEngine)
from repro.serve.cache import DeviceRepStore, UserRepCache
from repro.serve.reference import SCORE_TOL, ReferenceScorer


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


def _assert_bit_identical(per, co):
    for p, c in zip(per, co):
        assert p.scores.shape == c.scores.shape
        assert np.array_equal(p.scores, c.scores), (
            f"coalesced diverged: max diff "
            f"{np.abs(p.scores - c.scores).max()}")


_REFERENCES: dict = {}


def _assert_matches_reference(graph, params, reqs, *results):
    """Every result list scores each request within the stated CPU
    tolerance of the float32 reference on that request's own feeds."""
    key = (id(graph), id(params))
    if key not in _REFERENCES:
        _REFERENCES[key] = (graph, params, ReferenceScorer(graph, params))
    ref = _REFERENCES[key][2]
    atol, rtol = SCORE_TOL["cpu"]
    for i, req in enumerate(reqs):
        want = ref(req)
        for res in results:
            np.testing.assert_allclose(res[i].scores, want, atol=atol,
                                       rtol=rtol)


class TestUserRepCache:
    def test_lru_bound_and_evictions(self):
        c = UserRepCache(max_users=2)
        c.put((1, 0), {"x": 1})
        c.put((2, 0), {"x": 2})
        c.get((1, 0))                      # 1 is now most recent
        c.put((3, 0), {"x": 3})            # evicts LRU user 2
        assert c.evictions == 1
        assert (2, 0) not in c and (1, 0) in c and (3, 0) in c

    def test_version_supersede_not_counted_as_eviction(self):
        c = UserRepCache(max_users=8)
        c.put((1, 0), {"x": 1})
        c.put((1, 1), {"x": 2})
        assert len(c) == 1 and (1, 1) in c
        assert c.evictions == 0            # supersede, not capacity pressure

    def test_invalidate_user(self):
        c = UserRepCache()
        c.put((1, 0), {})
        c.put((2, 0), {})
        assert c.invalidate_user(1) == 1
        assert (1, 0) not in c and (2, 0) in c

    def test_unbounded_by_default(self):
        c = UserRepCache()
        for u in range(100):
            c.put((u, 0), {})
        assert len(c) == 100 and c.evictions == 0

    def test_engine_surfaces_evictions(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=32,
                            max_cached_users=2)
        for uid in range(4):
            eng.score(_request(graph, user_in, uid, 9, seed=uid))
        assert len(eng.cache) == 2
        assert eng.cache_evictions == 2
        # evicted user recomputes stage 1; resident user hits
        assert not eng.score(
            _request(graph, user_in, 0, 9, seed=0)).user_cache_hit
        assert eng.score(
            _request(graph, user_in, 3, 9, seed=3)).user_cache_hit


class TestCoalescedLossless:
    """Scores from the batcher (many users coalesced into one bucket) and
    from per-request ``score()`` — differently shaped executables — must
    both match the float32 reference within the stated tolerance: ragged
    tails, chunked pools, and cache hits/misses mixed in one batch."""

    @pytest.mark.parametrize("mode", ["vani", "uoi", "mari"])
    def test_modes_bit_identical(self, paper, mode):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode=mode, max_batch=128)
        reqs = [_request(graph, user_in, 0, 23, seed=1),
                _request(graph, user_in, 1, 40, seed=2),
                _request(graph, user_in, 2, 7, seed=3),
                _request(graph, user_in, 0, 31, seed=4),   # repeat user
                _request(graph, user_in, 3, 64, seed=5)]
        per = [eng.score(r) for r in reqs]
        # max_coalesce == len(reqs) closes the group deterministically once
        # all requests are queued (no reliance on linger timing under load)
        with CoalescingBatcher(eng, linger_ms=2000.0,
                               max_coalesce=len(reqs)) as b:
            co = b.score_many(reqs)
        # two-stage engines serve the repeat user from user 0's cached
        # reps: its reference then runs on the first request's user feeds
        # (single-stage vani recomputes from the request's own feeds)
        if eng.two_stage:
            reqs[3] = ServeRequest(0, reqs[0].user_feeds,
                                   reqs[3].candidate_feeds)
        _assert_matches_reference(graph, params, reqs, per, co)
        assert eng.coalesced_calls >= 1
        assert b.coalesced_requests == len(reqs)

    def test_mixed_hits_and_misses_one_batch(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=256)
        warm = _request(graph, user_in, 7, 20, seed=7)
        ref_warm = eng.score(warm)                  # user 7 now cached
        fresh = [_request(graph, user_in, 8, 33, seed=8),
                 _request(graph, user_in, 9, 12, seed=9)]
        ref_fresh = [ServingEngine(graph, params, mode="mari",
                                   max_batch=256).score(r) for r in fresh]
        co = eng.score_coalesced([warm] + fresh)
        assert co[0].user_cache_hit and not co[1].user_cache_hit
        _assert_matches_reference(graph, params, [warm] + fresh,
                                  [ref_warm] + ref_fresh, co)
        assert all(r.coalesced for r in co)

    def test_pool_larger_than_max_batch_spills_chunks(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=64,
                            min_bucket=16)
        reqs = [_request(graph, user_in, 0, 150, seed=1),   # 64+64+22
                _request(graph, user_in, 1, 30, seed=2)]    # tail shares
        per = [eng.score(r) for r in reqs]
        co = eng.score_coalesced(reqs)
        _assert_matches_reference(graph, params, reqs, per, co)
        # the 22-row tail and the 30-row pool coalesce into one 64 bucket
        assert co[0].n_batches == 3 and co[1].n_batches == 1
        assert eng.coalesced_calls >= 1

    def test_din_reparam_attention_coalesced(self):
        graph, _ = build_din(embed_dim=8, seq_len=12, attn_mlp=(16, 8),
                             mlp=(24, 12), item_vocab=128)
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        user_in = {n.name for n in graph.input_nodes()
                   if n.attrs.get("domain") == "user"}
        eng = ServingEngine(graph, params, mode="mari", max_batch=64,
                            min_bucket=8, reparam_attention=True)
        reqs = [_request(graph, user_in, u, n, seed=u + 1)
                for u, n in ((0, 11), (1, 17), (2, 5))]
        per = [eng.score(r) for r in reqs]
        co = eng.score_coalesced(reqs)
        _assert_matches_reference(graph, params, reqs, per, co)

    def test_single_stage_fallback_coalesced(self):
        """A graph that cannot split (domain-less input in the user closure)
        serves single-stage; coalescing gathers raw user feeds row-wise and
        must still match the reference."""
        from repro.graph.ir import GraphBuilder
        b = GraphBuilder()
        u = b.input("u", (6,), "user")
        ctx = b.input("ctx", (4,), None)
        i = b.input("i", (5,), "item")
        uc = b.concat("uc", [u, ctx])
        c = b.concat("c", [uc, i])
        f = b.dense("f", c, 8, activation="relu")
        out = b.dense("out", f, 1)
        b.output(out)
        graph = b.graph
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        eng = ServingEngine(graph, params, mode="mari", max_batch=32,
                            min_bucket=8)
        assert not eng.two_stage
        ks = jax.random.split(jax.random.PRNGKey(1), 12)
        reqs = []
        for uid, n in ((0, 5), (1, 9), (2, 3)):
            reqs.append(ServeRequest(
                uid,
                {"u": jax.random.normal(ks[2 * uid], (1, 6)),
                 "ctx": jax.random.normal(ks[2 * uid + 1], (1, 4))},
                {"i": jax.random.normal(ks[6 + uid], (n, 5))}))
        per = [eng.score(r) for r in reqs]
        co = eng.score_coalesced(reqs)
        _assert_matches_reference(graph, params, reqs, per, co)

    def test_compiled_shape_family_bounded(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=128)
        for n in (10, 50, 100):
            eng.score(_request(graph, user_in, 0, n, seed=n))
        eng.score_coalesced([_request(graph, user_in, u, 20, seed=u)
                             for u in range(3)])
        # U=1 (per-request) and U_pad=4 (3 users) at one bucket each
        assert eng.stage2_compilations <= 2


@pytest.fixture(scope="module")
def din():
    graph, _ = build_din(embed_dim=8, seq_len=12, attn_mlp=(16, 8),
                         mlp=(24, 12), item_vocab=128)
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    return graph, params, user_in


class TestGatherAttention:
    """Gather-aware attention (``gather_attention``): stage 2 consumes the
    decomposed-attention boundary tensors as stacked (U, ...) tables + a
    per-row user index, the gather folded into the contractions
    (``kernels.gather_einsum``), so the (B, L, D, h)-class gathered user
    blocks never materialize — while scores stay exact."""

    def _engine(self, din_fixture, **kw):
        graph, params, _ = din_fixture
        return ServingEngine(graph, params, mode="mari", max_batch=64,
                             min_bucket=8, reparam_attention=True, **kw)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_coalesced_bit_identical_and_matches_gather_off(
            self, din, use_pallas):
        graph, params, user_in = din
        eng = self._engine(din, gather_attention=True, use_pallas=use_pallas)
        # the attention boundary tensors actually ride the stacked path
        assert {"din_attn::T", "din_attn::u_part",
                "user_seq_emb"} <= eng.lazy_gather_inputs
        reqs = [_request(graph, user_in, u, n, seed=u + 1)
                for u, n in ((0, 11), (1, 17), (2, 5))]
        per = [eng.score(r) for r in reqs]
        co = eng.score_coalesced(reqs)
        # U=1 and the coalesced pack are differently shaped executables
        _assert_matches_reference(graph, params, reqs, per, co)
        assert eng.coalesced_calls >= 1
        off = self._engine(din, gather_attention=False,
                           use_pallas=use_pallas)
        for c, r in zip(co, off.score_coalesced(reqs)):
            np.testing.assert_allclose(c.scores, r.scores,
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("mode", ["vani", "uoi", "mari"])
    def test_modes_u1_vs_coalesced_bit_identical(self, din, mode):
        """Flag on in EVERY mode: mari exercises the gather path; vani/uoi
        have no decomposed attention (the flag is a no-op) — U=1 and
        coalesced scores stay within tolerance of the reference."""
        graph, params, user_in = din
        eng = ServingEngine(graph, params, mode=mode, max_batch=64,
                            min_bucket=8, reparam_attention=True,
                            gather_attention=True)
        if mode != "mari":
            assert not eng.lazy_gather_inputs
        reqs = [_request(graph, user_in, u, n, seed=u + 7)
                for u, n in ((0, 9), (1, 21), (2, 13))]
        per = [eng.score(r) for r in reqs]
        co = eng.score_coalesced(reqs)
        _assert_matches_reference(graph, params, reqs, per, co)

    def test_sharded_gather_attention_matches_unsharded(self, din):
        """Candidate-axis sharding composes with the stacked-table path:
        (U, ...) tables replicate, the index shards, and no (B, ...) user
        block is ever all-gathered."""
        graph, params, user_in = din
        sh = self._engine(din, gather_attention=True, shard_candidates=True)
        ref = self._engine(din, gather_attention=True)
        reqs = [_request(graph, user_in, u, n, seed=u + 1)
                for u, n in ((0, 21), (1, 12))]
        _assert_bit_identical(ref.score_coalesced(reqs),
                              sh.score_coalesced(reqs))

    def test_out_of_range_user_index_clamps(self, din):
        """Padded-row hazard (the batcher pads ``user_index`` alongside the
        candidate rows): a poisoned index must CLAMP to the last real slot
        — with U=3 and index 7, wrapping would read slot 1 and jax's
        default take would NaN-fill the row; both are caught here."""
        graph, params, user_in = din
        eng = self._engine(din, gather_attention=True)
        reqs = [_request(graph, user_in, u, 4, seed=u + 1) for u in range(3)]
        eng.score_coalesced(reqs)                  # warm the rep cache
        reps = [eng.cache.get((u, 0)) for u in range(3)]
        table = {k: jnp.concatenate([r[k] for r in reps], axis=0)
                 for k in reps[0]}                 # U=3, deliberately non-pow2
        cand = {k: jnp.concatenate(
                    [r.candidate_feeds[k] for r in reqs], axis=0)
                for k in reqs[0].candidate_feeds}  # 12 rows
        good = np.repeat(np.arange(3, dtype=np.int32), 4)
        bad = good.copy()
        bad[-4:] = 7                               # clip->2 (== good), wrap->1
        out_bad = eng._stage2(eng._params_s2, table, jnp.asarray(bad), cand)
        out_good = eng._stage2(eng._params_s2, table, jnp.asarray(good), cand)
        for o in eng.outputs:
            assert np.isfinite(np.asarray(out_bad[o])).all()
            np.testing.assert_array_equal(np.asarray(out_bad[o]),
                                          np.asarray(out_good[o]))


class TestSingleStageCacheBypass:
    """Single-stage serving (vani, or an unsplittable graph) has no stage-1
    outputs to reuse — the rep cache must be a complete no-op there, not
    bookkeeping overhead on the hot path."""

    def test_vani_never_touches_cache(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="vani", max_batch=32)
        assert not eng.two_stage and not eng.cache_user_reps
        for uid in range(3):
            r = eng.score(_request(graph, user_in, uid, 9, seed=uid))
            assert not r.user_cache_hit
        eng.score(_request(graph, user_in, 0, 9, seed=0))   # repeat user
        assert len(eng.cache) == 0
        assert eng.cache.hits == 0 and eng.cache.misses == 0

    def test_two_stage_still_caches(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=32)
        assert eng.cache_user_reps
        eng.score(_request(graph, user_in, 5, 9, seed=5))
        assert eng.score(
            _request(graph, user_in, 5, 9, seed=5)).user_cache_hit


class TestPrecatWeights:
    """Grouped-weight pre-concat at engine build must not change a single
    bit — the streamed operands are identical, only the concat moves out of
    the per-call path."""

    @pytest.mark.parametrize("use_pallas", [False, True])
    @pytest.mark.parametrize("layout", ["group_by_domain", "fragment"])
    def test_bit_identical(self, paper, layout, use_pallas):
        graph, params, user_in = paper
        kw = {layout: True}
        engines = [ServingEngine(graph, params, mode="mari", max_batch=64,
                                 precat_weights=p, use_pallas=use_pallas,
                                 **kw) for p in (False, True)]
        reqs = [_request(graph, user_in, u, n, seed=u + 1)
                for u, n in ((0, 21), (1, 40))]
        r_off = engines[0].score_coalesced(reqs)
        r_on = engines[1].score_coalesced(reqs)
        _assert_bit_identical(r_off, r_on)

    def test_w_cat_present_on_stage2_nodes(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=64,
                            group_by_domain=True)
        cats = [name for name, p in eng.params.items()
                if isinstance(p, dict) and "w_cat" in p]
        assert cats, "expected pre-concatenated weights on rewritten nodes"
        for name in cats:
            node = eng.split.stage2.nodes[name]
            ws = [eng.params[name][f"w_{lab}"]
                  for lab, _ in node.attrs["groups"] if lab != "user"]
            assert eng.params[name]["w_cat"].shape[0] == sum(
                w.shape[0] for w in ws)


class TestShardedStage2:
    def test_single_device_bit_identical(self, paper):
        graph, params, user_in = paper
        ref = ServingEngine(graph, params, mode="mari", max_batch=64)
        sh = ServingEngine(graph, params, mode="mari", max_batch=64,
                           shard_candidates=True)
        reqs = [_request(graph, user_in, u, n, seed=u + 1)
                for u, n in ((0, 21), (1, 40))]
        _assert_bit_identical(ref.score_coalesced(reqs),
                              sh.score_coalesced(reqs))

    def test_multi_device_subprocess(self):
        """Real candidate-axis sharding over 8 forced host devices: sharded
        coalesced scores must match the unsharded engine."""
        script = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, numpy as np
assert len(jax.devices()) == 8
from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.serve import ServeRequest, ServingEngine

graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.03))
params = init_graph_params(graph, jax.random.PRNGKey(0))
user_in = {n.name for n in graph.input_nodes()
           if n.attrs.get("domain") == "user"}
def req(uid, n, seed):
    feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(seed))
    return ServeRequest(uid, {k: v for k, v in feeds.items() if k in user_in},
                        {k: v for k, v in feeds.items() if k not in user_in})
reqs = [req(0, 21, 1), req(1, 40, 2), req(2, 9, 3)]
ref = ServingEngine(graph, params, mode="mari", max_batch=64, min_bucket=16)
sh = ServingEngine(graph, params, mode="mari", max_batch=64, min_bucket=16,
                   shard_candidates=True)
assert sh.mesh.devices.size == 8, sh.mesh
a = ref.score_coalesced(reqs)
b = sh.score_coalesced(reqs)
for x, y in zip(a, b):
    np.testing.assert_allclose(x.scores, y.scores, rtol=1e-6, atol=1e-6)
print("SHARDED-OK")
"""
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        assert "SHARDED-OK" in p.stdout


class TestBatcherRuntime:
    def test_burst_coalesces_into_few_batches(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=256)
        reqs = [_request(graph, user_in, u, 20, seed=u) for u in range(6)]
        eng.score(reqs[0])                       # compile before timing paths
        # group closes at max_coalesce, not on linger expiry — deterministic
        # even when the submitting thread stalls under suite load
        with CoalescingBatcher(eng, linger_ms=2000.0, max_coalesce=3) as b:
            results = b.score_many(reqs)
        assert all(r.scores.shape[0] == 20 for r in results)
        assert b.batches < len(reqs)             # actually coalesced
        assert b.requests == len(reqs)

    def test_submit_returns_future(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=64)
        with CoalescingBatcher(eng, linger_ms=1.0) as b:
            fut = b.submit(_request(graph, user_in, 0, 10, seed=1))
            res = fut.result(timeout=120)
        assert res.scores.shape[0] == 10

    def test_error_propagates_to_waiters(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=64)
        bad = ServeRequest(0, {}, {"item_feats": np.zeros((4, 3))})
        with CoalescingBatcher(eng, linger_ms=1.0) as b:
            fut = b.submit(bad)
            with pytest.raises(Exception):
                fut.result(timeout=120)

    def test_closed_batcher_rejects(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, mode="mari", max_batch=64)
        b = CoalescingBatcher(eng, auto_start=False)
        with pytest.raises(RuntimeError):
            b.submit(_request(graph, user_in, 0, 10, seed=1))


class _GatedSpyEngine:
    """Engine stand-in recording dispatch order; the FIRST group blocks
    until released, so requests submitted meanwhile pile up in the queue
    and their pop order becomes observable."""
    max_batch = 1 << 30

    def __init__(self):
        self.groups: list[list[int]] = []
        self.gate = threading.Event()

    def new_group_id(self):
        return None

    def score_coalesced(self, reqs, gid=None):
        self.groups.append([r.user_id for r in reqs])
        if len(self.groups) == 1:
            self.gate.wait(timeout=30)
        return [object()] * len(reqs)


class TestDeadlineScheduling:
    def test_deadline_request_jumps_queued_best_effort(self):
        """A deadline-tagged request submitted AFTER older best-effort
        ones is dispatched before them (priority pop, FIFO within class)."""
        spy = _GatedSpyEngine()
        req = lambda uid: ServeRequest(uid, {}, {"x": np.zeros((4, 2))})
        b = CoalescingBatcher(spy, linger_ms=0.0, max_coalesce=1)
        try:
            blocker = b.submit(req(99))
            for _ in range(300):             # worker holds group 1 open
                if spy.groups:
                    break
                time.sleep(0.01)
            assert spy.groups == [[99]]
            futs = [b.submit(req(uid)) for uid in (1, 2, 3)]
            futs.append(b.submit(req(9), slo="deadline"))
            spy.gate.set()
            for f in [blocker] + futs:
                f.result(timeout=30)
        finally:
            spy.gate.set()
            b.close()
        # deadline request 9 overtook the older best-effort 1, 2, 3
        assert spy.groups == [[99], [9], [1], [2], [3]]
        assert b.deadline_requests == 1

    def test_deadline_ms_implies_class_and_caps_linger(self):
        spy = _GatedSpyEngine()
        spy.gate.set()                       # never hold groups open
        b = CoalescingBatcher(spy, linger_ms=100.0, auto_start=False)
        from repro.serve.batcher import _PRIO, _Item, SLO_DEADLINE
        now = time.perf_counter()
        # deadline class shrinks the linger window to linger * frac
        it = _Item(prio=_PRIO[SLO_DEADLINE], seq=1)
        assert b._linger_until(it, now) - now == pytest.approx(
            0.1 * b.deadline_linger_frac, rel=1e-6)
        # a near-expiry deadline caps it further
        it2 = _Item(prio=_PRIO[SLO_DEADLINE], seq=2, deadline_at=now + 0.001)
        assert b._linger_until(it2, now) - now == pytest.approx(0.001,
                                                                rel=1e-6)
        # best-effort keeps the full linger
        it3 = _Item(prio=1, seq=3)
        assert b._linger_until(it3, now) - now == pytest.approx(0.1,
                                                                rel=1e-6)

    def test_bad_slo_rejected(self):
        spy = _GatedSpyEngine()
        spy.gate.set()
        b = CoalescingBatcher(spy, linger_ms=0.0)
        try:
            with pytest.raises(ValueError, match="SLO"):
                b.submit(ServeRequest(0, {}, {"x": np.zeros((2, 2))}),
                         slo="gold-plated")
        finally:
            b.close()


class TestDeviceRepStore:
    """The slot-allocated device tier in isolation: donated row writes,
    LRU steals honoring protection, drop-recycling, byte accounting."""

    @staticmethod
    def _reps(val, d=4):
        return {"a": jnp.full((1, d), float(val)),
                "b": jnp.full((1, 2, 3), float(val) + 0.5)}

    def test_slot_lifecycle_and_row_contents(self):
        st = DeviceRepStore(capacity=3)
        slots = st.ensure_rows([(1, 0, self._reps(1)),
                                (2, 0, self._reps(2))])
        assert slots == [0, 1] and st.writes == 2 and len(st) == 2
        # live (user, version): LRU bump, no write
        assert st.ensure_rows([(1, 0, self._reps(99))]) == [0]
        assert st.writes == 2 and st.hits == 1
        # the skipped write means the table still holds user 1's ORIGINAL
        # row — same-version reps are immutable by cache contract
        np.testing.assert_array_equal(
            np.asarray(st.tables["a"][0]), np.full((4,), 1.0))
        np.testing.assert_array_equal(
            np.asarray(st.tables["b"][1]), np.full((2, 3), 2.5))
        # version supersede rewrites the user's OWN slot in place
        assert st.ensure_rows([(1, 1, self._reps(7))]) == [0]
        assert st.writes == 3 and len(st) == 2
        np.testing.assert_array_equal(
            np.asarray(st.tables["a"][0]), np.full((4,), 7.0))

    def test_lru_steal_respects_protection(self):
        st = DeviceRepStore(capacity=2)
        st.ensure_rows([(1, 0, self._reps(1)), (2, 0, self._reps(2))])
        # user 1 is LRU but protected -> user 2's slot is stolen instead
        slots = st.ensure_rows([(3, 0, self._reps(3))], protect=[1])
        assert slots == [1] and st.recycles == 1
        assert st.slot_of(2) is None and st.slot_of(1) == 0
        # everything protected and no free slot -> overflow, not a steal
        slots = st.ensure_rows([(4, 0, self._reps(4))], protect=[1, 3])
        assert slots == [None] and st.overflows == 1
        assert len(st) == 2

    def test_drop_recycles_slot_without_touching_rows(self):
        st = DeviceRepStore(capacity=2)
        st.ensure_rows([(1, 0, self._reps(1)), (2, 0, self._reps(2))])
        st.drop(1)
        assert st.drops == 1 and len(st) == 1 and st.slot_of(1) is None
        # dead row contents are untouched (never zeroed) ...
        np.testing.assert_array_equal(
            np.asarray(st.tables["a"][0]), np.full((4,), 1.0))
        # ... and the freed slot integer is recycled by the next user
        assert st.ensure_rows([(5, 0, self._reps(5))]) == [0]
        np.testing.assert_array_equal(
            np.asarray(st.tables["a"][0]), np.full((4,), 5.0))

    def test_spec_validation_and_stats(self):
        st = DeviceRepStore(capacity=2, boundary_specs={"a": (4,),
                                                        "b": (2, 3)})
        with pytest.raises(ValueError, match="shape"):
            st.ensure_rows([(1, 0, {"a": jnp.zeros((1, 5)),
                                    "b": jnp.zeros((1, 2, 3))})])
        st.ensure_rows([(1, 0, self._reps(1))])
        s = st.stats()
        assert s["capacity"] == 2 and s["resident"] == 1
        assert s["free_slots"] == 1 and s["writes"] == 1
        # bytes account the FULL persistent tables, not one row
        expect = 2 * (4 + 2 * 3) * 4
        assert s["bytes"] == expect
        assert s["boundary_bytes"] == {"a": 2 * 4 * 4, "b": 2 * 6 * 4}


class TestDeviceResidentTier:
    """CachePlan.device_resident end to end: persistent device tables +
    donated bucket buffers must score like the re-stacking path (both
    within tolerance of the float32 reference: the (capacity, ...) tables
    change the executable's shapes), across engine paradigms, coalesced
    multi-user packs, eviction churn, scoped invalidation, and
    dead/out-of-range slots."""

    PRESETS = {"vani": "vanilla", "uoi": "uoi", "mari": "paper"}

    def _plan(self, preset, **evolve):
        base = dict(batch__max_batch=64, batch__min_bucket=8)
        base.update(evolve)
        return ServePlan.preset(preset).evolve(**base)

    @pytest.mark.parametrize("mode", ["vani", "uoi", "mari"])
    def test_bit_identical_to_restacking(self, paper, mode):
        graph, params, user_in = paper
        ref = ServingEngine(graph, params, plan=self._plan(
            self.PRESETS[mode]))
        dev = ServingEngine(graph, params, plan=self._plan(
            self.PRESETS[mode], cache__device_resident=True))
        reqs = [_request(graph, user_in, u, n, seed=u + 7)
                for u, n in ((0, 21), (1, 40), (2, 12))]
        per_ref = [ref.score(r) for r in reqs]
        per_dev = [dev.score(r) for r in reqs]
        # coalesced multi-user pack over the SAME persistent tables (all
        # three users already resident -> zero new row writes)
        _assert_matches_reference(graph, params, reqs, per_ref, per_dev,
                                  dev.score_coalesced(reqs))
        if dev.two_stage:
            assert dev.device_resident and dev.device_store is not None
            assert dev.device_store.writes == 3
            assert len(dev.device_store) == 3
        else:
            # single-stage: no reps to keep resident — runtime gates the
            # tier off even though the plan asked for it
            assert not dev.device_resident and dev.device_store is None
        ref.close()
        dev.close()

    def test_eviction_churn_keeps_scores_exact(self, paper):
        """Host-tier LRU evictions recycle device slots via the removal
        listener; scores through the churn stay exact."""
        graph, params, user_in = paper
        ref = ServingEngine(graph, params, plan=self._plan("paper"))
        dev = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True,
            cache__max_cached_users=2, cache__device_slots=2))
        reqs = [_request(graph, user_in, u, 12, seed=u) for u in range(5)]
        check = lambda r: _assert_matches_reference(
            graph, params, [r], [ref.score(r)], [dev.score(r)])
        for r in reqs:                       # cold sweep: 3 evictions
            check(r)
        st = dev.device_store.stats()
        assert st["resident"] <= 2 and st["drops"] >= 3
        assert dev.cache.evictions >= 3
        # users 3,4 are live; re-scoring is a hit with NO new write,
        # user 0 was evicted and re-runs stage 1 into a recycled slot
        writes = st["writes"]
        check(reqs[4])
        assert dev.device_store.writes == writes
        check(reqs[0])
        assert dev.device_store.writes == writes + 1
        ref.close()
        dev.close()

    def test_scoped_invalidation_frees_slot(self, paper):
        """Engine-level invalidation under a cache scope reaches the
        device tier through the scoped listener key."""
        graph, params, user_in = paper
        dev = ServingEngine(graph, params,
                            plan=self._plan("paper",
                                            cache__device_resident=True),
                            cache=UserRepCache(max_users=8),
                            cache_scope="sA")
        r = _request(graph, user_in, 5, 12, seed=5)
        first = dev.score(r)
        assert dev.device_store.slot_of(("sA", 5)) is not None
        dev.invalidate_user(5)
        assert dev.device_store.slot_of(("sA", 5)) is None
        assert dev.device_store.drops == 1 and len(dev.device_store) == 0
        again = dev.score(r)                 # re-runs stage 1, re-writes
        assert not again.user_cache_hit
        assert dev.device_store.writes == 2
        np.testing.assert_array_equal(first.scores, again.scores)
        dev.close()

    def test_dead_and_out_of_range_slots_clamp(self, paper):
        """The safety contract of never zeroing dead rows: unreferenced
        slots can't perturb live rows, and an out-of-range index clamps
        (mode="clip") instead of faulting."""
        graph, params, user_in = paper
        dev = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True, cache__device_slots=4))
        r1 = _request(graph, user_in, 1, 16, seed=1)
        r2 = _request(graph, user_in, 2, 16, seed=2)
        s1, s2 = dev.score(r1), dev.score(r2)
        dev.invalidate_user(1)               # slot 0 is now dead
        s2b = dev.score(r2)                  # reads table with a dead row
        np.testing.assert_array_equal(s2.scores, s2b.scores)
        # direct stage-2 probe: indices past capacity clamp to the last
        # slot; negative indices clamp to slot 0. The stage-2 executable
        # donates uidx+cand, so every call gets fresh arrays.
        table = dev.device_store.tables
        cap = dev.device_store.capacity
        chunk = {k: np.asarray(v)
                 for k, v in r2.candidate_feeds.items()}
        mk_cand = lambda: {k: jnp.array(v) for k, v in chunk.items()}
        run = lambda idx: {
            k: np.asarray(v) for k, v in dev._stage2(
                dev._params_s2, table,
                jnp.array(np.full((16,), idx, np.int32)),
                mk_cand()).items()}
        out_hi, out_last = run(cap + 3), run(cap - 1)
        out_neg, out_zero = run(-5), run(0)
        for o in dev.outputs:
            np.testing.assert_array_equal(out_hi[o], out_last[o])
            np.testing.assert_array_equal(out_neg[o], out_zero[o])
        dev.close()

    def test_mixed_version_same_user_falls_back(self, paper):
        """One coalesced call carrying the SAME user under two feature
        versions: the device store keeps one slot per user, so resolving
        the second version would rewrite the slot the first version's
        rows read. Every pack touching that user must fall back to
        re-stacking — both versions packed together and split across
        packs — and score like the re-stacking engine, each version
        against its own feeds' float32 reference."""
        graph, params, user_in = paper
        mk = lambda: [  # (user 1, v0), (user 1, v1), (user 2, v0)
            _request(graph, user_in, 1, 12, seed=11, version=0),
            _request(graph, user_in, 1, 12, seed=12, version=1),
            _request(graph, user_in, 2, 12, seed=13)]
        ref = ServingEngine(graph, params, plan=self._plan("paper"))
        # single pack: both versions' slot keys land in one ensure_rows
        one = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True))
        # split packs: a later pack's barrier write must not clobber a
        # slot an earlier pack references
        split = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True,
            batch__max_users_per_batch=1))
        _assert_matches_reference(graph, params, mk(),
                                  ref.score_coalesced(mk()),
                                  one.score_coalesced(mk()),
                                  split.score_coalesced(mk()))
        # a version-clean follow-up call goes device-resident again
        follow = _request(graph, user_in, 3, 12, seed=14)
        _assert_matches_reference(graph, params, [follow],
                                  [ref.score(follow)], [one.score(follow)])
        assert one.device_store.writes >= 1
        ref.close()
        one.close()
        split.close()

    def test_feed_signature_drift_fails_fast(self, paper):
        """Staging buffers are shaped from the first request; a later
        request with a drifting candidate dtype must raise before any
        launch instead of being silently cast by the buffer fill."""
        graph, params, user_in = paper
        dev = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True))
        dev.score(_request(graph, user_in, 1, 8, seed=1))
        drifted = _request(graph, user_in, 2, 8, seed=2)
        k = next(iter(drifted.candidate_feeds))
        drifted.candidate_feeds = {
            **drifted.candidate_feeds,
            k: np.asarray(drifted.candidate_feeds[k], np.float64)}
        with pytest.raises(ValueError, match="signature drifted"):
            dev.score(drifted)
        dev.close()

    def test_restack_fallback_on_slot_overflow(self, paper):
        """More users in one coalesced call than device slots: the
        overflowing pack falls back to re-stacking, bit-identically."""
        graph, params, user_in = paper
        ref = ServingEngine(graph, params, plan=self._plan("paper"))
        dev = ServingEngine(graph, params, plan=self._plan(
            "paper", cache__device_resident=True, cache__device_slots=2,
            batch__max_users_per_batch=4))
        reqs = [_request(graph, user_in, u, 8, seed=u + 3)
                for u in range(4)]
        _assert_matches_reference(graph, params, reqs,
                                  ref.score_coalesced(reqs),
                                  dev.score_coalesced(reqs))
        assert dev.device_store.overflows >= 1
        ref.close()
        dev.close()
