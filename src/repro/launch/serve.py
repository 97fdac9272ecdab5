"""Serving launcher: scores a stream of synthetic requests through the
serving runtime and reports latency stats. Configuration is a
``ServePlan`` (``repro.serve.plan``) — from a JSON file, a named preset,
or the flag overrides — instead of hand-threaded engine kwargs.

Single-scenario (one ``ServingEngine``)::

  python -m repro.launch.serve --arch din --mode mari --requests 20
  python -m repro.launch.serve --plan plan.json --requests 3
  python -m repro.launch.serve --preset tpu --dump-plan plan.json

Multi-scenario (a ``RankingService`` routing an interleaved stream)::

  python -m repro.launch.serve --scenario din,deepfm,fm --requests 12

``--smoke`` is on by default; ``--no-smoke`` builds the full-size
registry models.

``--trace out.json`` turns on ``ObsPlan.trace`` for the run and writes a
Chrome trace-event file (load it at https://ui.perfetto.dev) covering the
whole request lifecycle — stage-1 spans, cache hit/miss instants, pack/
dispatch/collect, and one synthetic track per outstanding group.

``--cold-tier`` arms the ``MemPlan`` host-RAM cold tier, bulk-warms the
even user ids of the synthetic stream into the cold arena, and reports
cold hits / async promotions after the stream — so a traced run emits
the ``warm`` / ``cold_hit`` / ``promote`` instants.

The synthetic stream draws user ids from a Zipf law over ``N_USERS`` ids,
so popular users repeat (cache hits); a user id always carries the same
user-side features, and every request brings a fresh candidate pool of
``--candidates`` rows.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.common import enable_compile_cache
from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.serve import RankingService, ServePlan, ServeRequest, ServingEngine
from repro.serve.plan import MODES, PRESETS


def build_plan(args) -> ServePlan:
    """Resolve the serving plan: file < preset < explicit flag overrides."""
    if args.plan and args.preset:
        raise SystemExit("pass --plan or --preset, not both")
    if args.plan:
        plan = ServePlan.load(args.plan)
    elif args.preset:
        plan = ServePlan.preset(args.preset)
    else:
        plan = ServePlan()
    over = {}
    if args.mode is not None:
        over["graph__mode"] = args.mode
    if args.max_batch is not None:
        over["batch__max_batch"] = args.max_batch
    if args.reparam_attention is not None:
        over["graph__reparam_attention"] = args.reparam_attention
    if args.gather_attention is not None:
        over["kernel__gather_attention"] = args.gather_attention
    if args.use_pallas is not None:
        over["kernel__use_pallas"] = args.use_pallas
    if args.continuous is not None:
        over["batch__continuous"] = args.continuous
    if args.cold_tier is not None:
        over["mem__cold_tier"] = args.cold_tier
    if args.device_resident is not None:
        over["cache__device_resident"] = args.device_resident
    if args.trace:
        over["obs__trace"] = True
    return plan.evolve(**over) if over else plan


# size of the synthetic stream's user-id universe
N_USERS = 8


def zipf_users(n: int, n_users: int = N_USERS, a: float = 1.1,
               seed: int = 7) -> list[int]:
    """``n`` user ids drawn from a Zipf(``a``) law over ``n_users`` ids
    (id 0 the most popular): the head users repeat."""
    p = 1.0 / np.arange(1, n_users + 1) ** a
    rng = np.random.default_rng(seed)
    return rng.choice(n_users, size=n, p=p / p.sum()).tolist()


def user_feeds(graph, split, uid: int, candidates: int, seed: int = 11):
    """User ``uid``'s fixed user-side features: one user id always
    carries the same feeds (the rep cache's keying contract)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), uid)
    return split(make_recsys_feeds(graph, candidates, key))[0]


def request_stream(graph, split, uids, candidates: int,
                   seed: int = 7) -> list[ServeRequest]:
    """One request per user id in ``uids``: the user's fixed features and
    a fresh ``candidates``-row pool."""
    key = jax.random.PRNGKey(seed)
    users, reqs = {}, []
    for r, uid in enumerate(uids):
        if uid not in users:
            users[uid] = user_feeds(graph, split, uid, candidates)
        cand = split(make_recsys_feeds(graph, candidates,
                                       jax.random.fold_in(key, r)))[1]
        reqs.append(ServeRequest(user_id=uid, user_feeds=users[uid],
                                 candidate_feeds=cand))
    return reqs


def _warm_half(warm, graph, split, candidates: int, n_uids: int):
    """Bulk-warm the EVEN user ids of the stream into the cold arena. Odd
    ids stay unwarmed, so one stream exercises every tier: even ids
    cold-hit (and, after enough touches, promote); odd ids pay stage 1
    once and then hot-hit."""
    return warm([(uid, user_feeds(graph, split, uid, candidates))
                 for uid in range(0, n_uids, 2)])


def _summary(tag: str, lats: list[float]) -> None:
    if not lats:        # e.g. more scenarios than requests in round-robin
        print(f"[serve] {tag} n=0 (no requests routed)")
        return
    lats = np.asarray(lats)
    print(f"[serve] {tag} n={len(lats)} "
          f"avg={lats.mean():.2f}ms p50={np.percentile(lats, 50):.2f}ms "
          f"p99={np.percentile(lats, 99):.2f}ms")


def serve_single(args, plan: ServePlan) -> None:
    from repro import configs as cfgreg
    mod = cfgreg.get_config(args.arch)
    build = mod.smoke_build() if args.smoke else mod.BUILD
    graph, *_ = build()
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    engine = ServingEngine(graph, params, plan=plan)
    if engine.conversion:
        print("[serve] MaRI rewrote:",
              [r.dense for r in engine.conversion.rewrites])

    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}

    def split(feeds):
        return ({k: v for k, v in feeds.items() if k in user_in},
                {k: v for k, v in feeds.items() if k not in user_in})

    if engine.cold_tier:
        warmed = _warm_half(engine.warm, graph, split, args.candidates,
                            N_USERS)
        print(f"[serve] warmed {warmed} users into the cold tier")
    lats = []
    for req in request_stream(graph, split,
                              zipf_users(args.requests),
                              args.candidates):
        lats.append(engine.score(req).latency_ms)
    if engine.cold_tier:
        engine.flush_promotions()
        ms = engine.mem_stats()
        print(f"[serve] mem cold_users={ms['cold']['users']} "
              f"cold_hits={ms['cold_hits']} "
              f"promotions={ms['promote']['promotions']}")
    if args.trace and engine.tracer is not None:
        from repro.obs import write_trace
        write_trace(args.trace, {args.arch: engine.tracer})
        print(f"[serve] wrote trace -> {args.trace} "
              f"({len(engine.tracer)} events, "
              f"{engine.tracer.dropped} dropped)")
    engine.close()
    _summary(f"arch={args.arch} mode={engine.mode}",
             lats[min(2, len(lats) - 1):])   # drop compile warmup


def serve_multi(args, plan: ServePlan, scenarios: list[str],
                inspect=None) -> None:
    """Route an interleaved request stream across several scenario models
    hosted by one ``RankingService`` (shared rep-cache budget, per-scenario
    engines + batchers). The whole stream is submitted at once, twice: a
    compile pass, then the timed pass.

    ``inspect(svc, items, passes, pass_s)``, if given, runs before the
    service closes, with the ``(scenario, request)`` stream, both passes'
    results and each pass's wall seconds."""
    with RankingService(plan, smoke=args.smoke) as svc:
        for sc in scenarios:
            svc.register(sc)
        print(f"[serve] scenarios={','.join(svc.scenarios)} "
              f"(interleaved round-robin)")
        for sc in scenarios:
            if svc.engine(sc).cold_tier:
                warmed = _warm_half(
                    lambda items, sc=sc: svc.warm(sc, items),
                    svc.source_graph(sc),
                    lambda feeds, sc=sc: svc.split_feeds(sc, feeds),
                    args.candidates, N_USERS)
                print(f"[serve] scenario={sc} warmed {warmed} users into "
                      f"the cold tier")
        uids = zipf_users(args.requests)
        n = len(scenarios)
        streams = {sc: iter(request_stream(
            svc.source_graph(sc),
            lambda feeds, sc=sc: svc.split_feeds(sc, feeds),
            uids[k::n], args.candidates)) for k, sc in enumerate(scenarios)}
        items = [(sc, next(streams[sc]))
                 for sc in (scenarios[r % n] for r in range(args.requests))]
        passes, pass_s = [], []
        for _ in range(2):                   # compile pass, timed pass
            t0 = time.perf_counter()
            passes.append(svc.score_many(items))
            pass_s.append(time.perf_counter() - t0)
        print(f"[serve] compile pass {pass_s[0]:.2f}s, "
              f"timed pass {pass_s[1]:.2f}s")
        per = {sc: [] for sc in scenarios}
        for (sc, _), res in zip(items, passes[1]):
            per[sc].append(res.latency_ms)
        for sc in scenarios:
            _summary(f"scenario={sc}", per[sc])
        cache = svc.stats()["shared_cache"]
        print(f"[serve] shared_cache users={cache['users']} "
              f"hits={cache['hits']} misses={cache['misses']} "
              f"evictions={cache['evictions']}")
        for sc in scenarios:
            eng = svc.engine(sc)
            if eng.cold_tier:
                eng.flush_promotions()
                ms = eng.mem_stats()
                print(f"[serve] scenario={sc} mem "
                      f"cold_users={ms['cold']['users']} "
                      f"cold_hits={ms['cold_hits']} "
                      f"promotions={ms['promote']['promotions']}")
        if args.trace:
            tracers = {sc: svc.engine(sc).tracer for sc in svc.scenarios
                       if svc.engine(sc).tracer is not None}
            if tracers:
                from repro.obs import write_trace
                write_trace(args.trace, tracers)
                n_ev = sum(len(t) for t in tracers.values())
                print(f"[serve] wrote trace -> {args.trace} "
                      f"({n_ev} events across {len(tracers)} scenarios)")
        if inspect is not None:
            inspect(svc, items, passes, pass_s)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="din",
                    help="single-scenario architecture (configs registry)")
    ap.add_argument("--scenario", default=None,
                    help="comma-separated scenario list — serves them all "
                         "through one RankingService (overrides --arch)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="load the ServePlan from a JSON file")
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None,
                    help="start from a named ServePlan preset")
    ap.add_argument("--dump-plan", default=None, metavar="PATH",
                    help="write the resolved plan JSON and continue")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--candidates", type=int, default=2048)
    # BooleanOptionalAction gives --smoke/--no-smoke; the old
    # action="store_true", default=True made the flag impossible to turn
    # off, so full-size builds were unreachable from the CLI
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="registry smoke builds (--no-smoke = full size)")
    # plan overrides: default None means "whatever the plan says"
    ap.add_argument("--mode", choices=list(MODES), default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--reparam-attention",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="mari: also re-parameterize eligible "
                         "target_attention units (beyond-paper rewrite)")
    ap.add_argument("--gather-attention",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="consume decomposed-attention boundary tensors as "
                         "stacked (U, ...) tables indexed inside the "
                         "contractions (gather-at-load; pairs with "
                         "--reparam-attention)")
    ap.add_argument("--use-pallas",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="route mari_dense + gather_einsum through the "
                         "Pallas kernels (interpret mode off-TPU)")
    ap.add_argument("--continuous",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="continuous (two-phase overlapped) dispatch loop "
                         "in the scenario batchers")
    ap.add_argument("--cold-tier",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="host-RAM cold rep tier (MemPlan): bulk-warm the "
                         "even user ids of the stream, serve cold hits "
                         "from the arena, promote hot users async")
    ap.add_argument("--device-resident",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="persistent device rep tables (CachePlan."
                         "device_resident) in place of per-pack re-stacking")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable ObsPlan tracing and write a Perfetto-"
                         "loadable Chrome trace-event JSON here")
    return ap.parse_args(argv)


def main(argv=None, inspect=None) -> None:
    """Run the launcher on ``argv`` (default: the command line).
    ``inspect`` is handed to ``serve_multi`` (``--scenario`` runs)."""
    args = parse_args(argv)
    plan = build_plan(args)
    if args.dump_plan:
        plan.save(args.dump_plan)
        print(f"[serve] wrote plan -> {args.dump_plan}")
    if args.requests < 1:
        return
    if args.scenario:
        # dedupe while preserving order: registering a scenario twice is a
        # service-level error, not something a CLI typo should crash on
        scenarios = list(dict.fromkeys(
            s for s in args.scenario.split(",") if s))
        if not scenarios:
            raise SystemExit("--scenario needs at least one scenario name")
        serve_multi(args, plan, scenarios, inspect)
    else:
        serve_single(args, plan)


if __name__ == "__main__":
    enable_compile_cache()
    main()
