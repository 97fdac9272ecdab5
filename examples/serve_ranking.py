"""End-to-end serving driver: the coarse-ranking stage of Fig. 2.

Part 1 — paradigm comparison: a stream of requests (one user, thousands of
candidates each) flows through the two-stage ServingEngine: the user-only
subgraph runs once per user and its outputs are cached (stage 1);
candidates are scored by the separately compiled batched residual (stage 2)
in power-of-two batch buckets. Compares the three inference paradigms of
Fig. 1 on the same request stream.

Part 2 — async cross-user coalescing: a simulated multi-user burst (ragged
pool sizes, mixed cache hits/misses) is submitted concurrently to the
``CoalescingBatcher``, which packs candidate chunks from different users
into shared stage-2 buckets — each executed as ONE row-wise call (every
candidate row gathers its own user's cached reps). Scores of both loops are
checked against the plain float32 reference within the stated tolerance
(``repro.serve.reference``); throughput is reported for both.

Part 3 — overload & SLO admission: the same graph behind a
``RankingService`` with the continuous dispatch loop and deliberately tiny
admission thresholds, hit with a burst far past what the queue will hold.
best_effort requests are shed (typed ``AdmissionError``, failing fast at
submit) or degraded (candidate pool truncated) while every deadline-tagged
request completes at full pool size — the SLO tiering in one printout.

Part 4 — hierarchical memory tier: the user universe is bulk-``warm``ed
OFFLINE into the host-RAM cold arena (``MemPlan.cold_tier``) through the
engine's own jitted stage 1, then the part-2 burst is replayed against a
deliberately tiny hot LRU. Every request is served from a tier — hot hit
or one cold-arena read — with zero online stage-1 recomputes, scores
bit-identical to the recompute path, and repeat traffic promoted back to
the hot tier by the async promotion worker.

  PYTHONPATH=src python examples/serve_ranking.py [--candidates 4096]
"""
import argparse
import time

import jax
import numpy as np

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.serve import (AdmissionError, CoalescingBatcher, RankingService,
                         SLO_DEADLINE, ReferenceScorer, ServePlan,
                         ServeRequest, ServingEngine)
from repro.serve.reference import tol_ratio, tolerance


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", type=int, default=4096)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--users", type=int, default=6)
    ap.add_argument("--max-batch", type=int, default=2048)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--linger-ms", type=float, default=3.0,
                    help="batcher linger window for collecting co-arriving "
                         "requests")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route mari_dense through the fused Pallas kernel "
                         "(interpret mode off-TPU: slow, validation only)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of parts "
                         "2+3 (coalescing + overload) — overlapped groups "
                         "show as concurrent group:N tracks")
    args = ap.parse_args()

    graph, cfg = build_paper_ranking_model(PaperRankingConfig().scaled(args.scale))
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}

    # user features are a function of the USER, not the request: the
    # rep-cache contract says one (user_id, feature_version) key maps to
    # one feature set. (The single-stage vani engine no longer caches raw
    # feeds, so a stream violating this would let vani see per-request
    # features while uoi/mari serve cached reps — stale-cache semantics,
    # not a paradigm difference.)
    user_feeds = {}

    def make_request(r, key, candidates):
        uid = r % args.users
        feeds = make_recsys_feeds(graph, candidates, key)
        if uid not in user_feeds:
            user_feeds[uid] = {k2: v for k2, v in feeds.items()
                               if k2 in user_in}
        return ServeRequest(
            user_id=uid,
            user_feeds=user_feeds[uid],
            candidate_feeds={k2: v for k2, v in feeds.items()
                             if k2 not in user_in})

    def request_stream(key):
        for r in range(args.requests):
            key, k = jax.random.split(key)
            yield make_request(r, k, args.candidates)

    # every served score is checked against the plain float32 reference
    # (un-rewritten graph, highest matmul precision) within the stated
    # tolerance: differently shaped executables are not bit-identical
    reference, tol = ReferenceScorer(graph, params), tolerance()

    def check(reqs, results, what):
        for req, res in zip(reqs, results):
            ratio = tol_ratio(res.scores, reference(req), tol)
            assert ratio <= 1.0, f"{what}: {ratio:.2f}x the tolerance {tol}"

    # ---- part 1: VanI vs UOI vs MaRI, sequential per-request loop ----------
    print(f"requests={args.requests} users={args.users} "
          f"candidates/request={args.candidates} max_batch={args.max_batch}")
    # ONE declarative plan, evolved per paradigm — the three engines differ
    # only in graph.mode (repro.serve.plan is the config spine)
    base_plan = ServePlan().evolve(batch__max_batch=args.max_batch,
                                   kernel__use_pallas=args.use_pallas)
    for mode in ("vani", "uoi", "mari"):
        eng = ServingEngine(graph, params,
                            plan=base_plan.evolve(graph__mode=mode))
        if eng.conversion:
            print(f"[{mode}] MaRI rewrote "
                  f"{len(eng.conversion.rewrites)} matmuls")
        if eng.two_stage:
            print(f"[{mode}] {eng.split.summary()}")
        lats, hits = [], 0
        for req in request_stream(jax.random.PRNGKey(42)):
            res = eng.score(req)
            lats.append(res.latency_ms)
            hits += res.user_cache_hit
        check([req], [res], mode)     # the last request of the stream
        lats = np.asarray(lats[2:])   # drop warm-up/compile
        extra = (f"  stage1_runs={eng.stage1_calls}"
                 f"  stage2_compiles={eng.stage2_compilations}"
                 if eng.two_stage else "")
        print(f"[{mode}] avg={lats.mean():7.2f}ms  "
              f"p50={np.percentile(lats, 50):7.2f}ms  "
              f"p99={np.percentile(lats, 99):7.2f}ms  "
              f"user_cache_hits={hits}/{args.requests}{extra}")
        eng.close()
    print(f"all modes within {tol} of the float32 reference ✓")

    # ---- part 2: async multi-user stream through the coalescing batcher ----
    print(f"\n-- async coalescing (mari): multi-user burst, ragged pools, "
          f"linger={args.linger_ms}ms --")
    eng = ServingEngine(graph, params, plan=base_plan.evolve(
        graph__mode="mari", obs__trace=args.trace is not None))
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(7), args.requests)
    burst = [make_request(r, keys[r],
                          int(rng.integers(args.candidates // 4,
                                           args.candidates)))
             for r in range(args.requests)]

    seq_results = [eng.score(r) for r in burst]      # warms every cache/shape
    t0 = time.perf_counter()
    for r in burst:
        eng.score(r)
    seq_s = time.perf_counter() - t0

    with CoalescingBatcher(eng, linger_ms=args.linger_ms) as batcher:
        co_results = batcher.score_many(burst)       # warm coalesced shapes
        # counters are lifetime-cumulative; snapshot so the printout
        # reflects only the timed burst
        calls0, cross0, batches0 = (eng.stage2_calls, eng.coalesced_calls,
                                    batcher.batches)
        t0 = time.perf_counter()
        co_results = batcher.score_many(burst)
        co_s = time.perf_counter() - t0
        calls = eng.stage2_calls - calls0
        cross = eng.coalesced_calls - cross0
        batches = batcher.batches - batches0

    check(burst, seq_results, "per-request")
    check(burst, co_results, "coalesced")
    rows = sum(r.scores.shape[0] for r in co_results)
    print(f"[sequential] {args.requests / seq_s:7.1f} req/s "
          f"({rows / seq_s:10.0f} candidates/s)")
    print(f"[coalesced ] {args.requests / co_s:7.1f} req/s "
          f"({rows / co_s:10.0f} candidates/s)  "
          f"stage2_calls/burst={calls}  "
          f"cross_user_calls={cross}  batches={batches}")
    print("per-request and coalesced scores within tolerance of the "
          "reference ✓")
    eng.close()

    # ---- part 3: overload burst against SLO-tiered admission control -------
    print("\n-- overload & admission (mari): burst past the queue, tiny "
          "shed/degrade depths --")
    # thresholds are deliberately small so a laptop-sized burst trips every
    # tier: shed best_effort beyond 8 queued, halve its candidate pool
    # beyond 4 queued; deadline-tagged requests are exempt from both
    over_plan = base_plan.evolve(
        graph__mode="mari", batch__continuous=True,
        batch__admission=True, batch__shed_queue_depth=8,
        batch__degrade_queue_depth=4, batch__degrade_frac=0.5,
        batch__linger_ms=args.linger_ms,
        obs__trace=args.trace is not None)
    svc = RankingService(over_plan)
    svc.register("ranking", graph=graph, params=params, plan=over_plan)
    for r in burst[:4]:                       # warm shapes + rep caches
        svc.score("ranking", r)

    futs = []
    for i, r in enumerate(burst * 3):         # ~3x the part-2 burst at once
        deadline = i % 5 == 0                 # every 5th request is urgent
        futs.append((deadline, svc.submit(
            "ranking", r, slo=SLO_DEADLINE if deadline else "best_effort",
            deadline_ms=250.0 if deadline else None)))
    # a shed future is already failed (fast, typed) when submit returns —
    # it never hangs; admitted futures resolve to ServeResults
    done, shed = [], 0
    for d, f in futs:
        err = f.exception()
        if err is not None:
            assert isinstance(err, AdmissionError), err
            assert not d, "deadline work must never be shed by depth"
            assert err.queue_depth >= 8, err
            shed += 1
        else:
            done.append((d, f.result()))
    assert all(not res.degraded for d, res in done if d), \
        "deadline work must never be degraded"
    degraded = sum(res.degraded for _, res in done)

    sc = svc.stats()["scenarios"]["ranking"]
    print(f"[burst     ] submitted={len(burst) * 3}  "
          f"completed={len(done)}  shed_at_submit={shed}  "
          f"degraded={degraded}")
    print(f"[counters  ] shed_best_effort={sc['shed_best_effort']}  "
          f"shed_deadline={sc['shed_deadline']}  "
          f"degraded_requests={sc['degraded_requests']}  "
          f"pipeline_forks={sc['pipeline_forks']}")
    print("deadline tier untouched under overload ✓")

    # ---- part 4: memory tier — warm offline, cold-hit online, promote -----
    print("\n-- memory tier (mari): bulk-warm offline, serve from the cold "
          "arena, promote repeat users --")
    # hot LRU deliberately smaller than the user universe: users live ONLY
    # in the host-RAM arena until the promotion worker sees repeat traffic
    mem_eng = ServingEngine(graph, params, plan=base_plan.evolve(
        graph__mode="mari", cache__max_cached_users=2,
        mem__cold_tier=True))
    warmed = mem_eng.warm(sorted(user_feeds.items()))
    warm_results = [mem_eng.score(r) for r in burst]
    hot = sum(r.user_cache_hit for r in warm_results)
    cold = sum(r.cold_hit for r in warm_results)
    assert mem_eng.stage1_calls == 0, \
        "warmed users must never pay stage 1 online"
    for w, s in zip(warm_results, seq_results):
        assert np.array_equal(w.scores, s.scores), \
            "warmed reps changed scores"
    mem_eng.flush_promotions()
    ms = mem_eng.mem_stats()
    print(f"[warm      ] users={warmed}  "
          f"arena_bytes={ms['cold']['bytes']}  "
          f"stage1_launches={ms['warm']['stage1_launches']}")
    print(f"[stream    ] hot_hits={hot}  cold_hits={cold}  "
          f"stage1_recomputes={mem_eng.stage1_calls}  "
          f"promotions={ms['promote']['promotions']}  "
          f"demotions={ms['demotions']}")
    print("every request tier-served, warmed reps bit-identical to "
          "recomputed ✓")
    mem_eng.close()
    if args.trace:
        from repro.obs import write_trace
        tracers = {}
        if eng.tracer is not None:
            tracers["coalesce"] = eng.tracer      # part 2 (events persist)
        t3 = svc.engine("ranking").tracer
        if t3 is not None:
            tracers["overload"] = t3              # part 3
        write_trace(args.trace, tracers)
        print(f"wrote trace -> {args.trace} "
              f"({sum(len(t) for t in tracers.values())} events; load it "
              f"at https://ui.perfetto.dev)")
    svc.close()


if __name__ == "__main__":
    main()
