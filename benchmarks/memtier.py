"""Memory-hierarchy benchmark: hit rate and latency vs universe size.

The question this answers: with a FIXED device budget (hot LRU slots +
device rep tables) and the default host-RAM cold budget, how far does the
hierarchical memory tier (``MemPlan``: cold arena + async promotion + bulk
warming) carry the serving hit rate as the user universe grows — up to
U=1M distinct users under the Zipf(1.1) popularity law production
rep-caches live on?

Per universe point it builds a fresh two-stage engine
(``mem__cold_tier=True``, device-resident hot tier), bulk-``warm``s the
Zipf head straight into the cold arena (capped by the arena's byte-budget
capacity), then serves a Zipf-sampled request stream and reports, per
request class:

* ``hot``       — hot-LRU hit (device-resident stage-2 fast path),
* ``cold``      — hot miss served from one cold-arena read (no stage-1
  recompute, re-stacking stage-2 path),
* ``recompute`` — full miss paying stage 1,

plus the combined hit rate (hot + cold over all requests), demotion /
promotion counters, and arena occupancy. A subset of every class is also
scored against a cache-off engine — bit-identity is part of the payload
and the ``check_mem_trend`` gate, not a footnote.

  python -m benchmarks.memtier --json BENCH_mem.json        # full sweep
  python -m benchmarks.memtier --smoke --json BENCH_mem_fresh.json  # CI

``--smoke`` runs the smallest universe point only (shared row names with
the committed baseline, so the trend gate can compare) with a shorter
stream. The acceptance numbers (U=1M at >= 0.9 combined hit rate, cold
strictly cheaper than recompute, bit-identical scores) live in the
committed ``BENCH_mem.json`` and are asserted by
``benchmarks.check_mem_trend`` against BOTH files.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.load import Workload, _quiesced_gc, sample_users, zipf_cdf

ZIPF_S = 1.1           # the harness's production-shaped popularity law
TARGET_MASS = 0.92     # warm the head up to this CDF mass (capacity-capped)
FULL_UNIVERSES = (10_000, 100_000, 1_000_000)
SMOKE_UNIVERSES = (10_000,)


def _build(seed: int = 0):
    import jax
    from repro.graph.executor import init_graph_params
    from repro.models.ranking import (PaperRankingConfig,
                                      build_paper_ranking_model)
    graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.05))
    params = init_graph_params(graph, jax.random.PRNGKey(seed))
    return graph, params


def _plan(cold_bytes: int | None):
    from repro.serve import ServePlan
    ev = dict(batch__linger_ms=0.0,
              cache__max_cached_users=4096,
              cache__device_resident=True, cache__device_slots=256,
              mem__cold_tier=True, mem__warm_batch=4096)
    if cold_bytes is not None:
        ev["mem__cold_bytes"] = cold_bytes
    return ServePlan.preset("paper").evolve(**ev)


def _warm_head(eng, wl: Workload, universe: int, cdf: np.ndarray) -> int:
    """Warm the Zipf head into the cold arena: up to TARGET_MASS of the
    popularity mass, capped by the arena's slot capacity (discovered from
    the first row, so probe with one user first)."""
    eng.warm([(0, wl.ufeeds[0])])
    capacity = eng.mem_stats()["cold"]["capacity"]
    k_cover = int(np.searchsorted(cdf, TARGET_MASS, side="left")) + 1
    k = min(universe, capacity, k_cover)
    if k > 1:
        eng.warm([(u, wl.ufeeds[u % len(wl.ufeeds)]) for u in range(1, k)])
    return k


def _class_stats(lat_us: list[float]) -> dict:
    if not lat_us:
        return {"n": 0, "p50_us": None, "p99_us": None}
    a = np.asarray(lat_us)
    return {"n": len(a),
            "p50_us": round(float(np.percentile(a, 50)), 1),
            "p99_us": round(float(np.percentile(a, 99)), 1)}


def run_point(graph, params, universe: int, requests: int, B: int,
              pool: int, cold_bytes: int | None, seed: int = 0,
              identity_engine=None, identity_n: int = 0) -> dict:
    from repro.serve import ServingEngine
    wl = Workload(graph, B, pool, seed=seed)
    eng = ServingEngine(graph, params, plan=_plan(cold_bytes))
    try:
        cdf = zipf_cdf(universe, ZIPF_S)
        t0 = time.perf_counter()
        warmed = _warm_head(eng, wl, universe, cdf)
        warm_s = time.perf_counter() - t0

        rng = np.random.default_rng(seed + 7)
        uids = sample_users(cdf, requests, rng)
        # compile + first-touch outside the timed stream
        eng.score(wl.req(int(uids[0])))

        lats: dict[str, list[float]] = {"hot": [], "cold": [],
                                        "recompute": []}
        identity = []          # (request, fresh scores) for the bit check
        with _quiesced_gc():
            for i, uid in enumerate(uids):
                req = wl.req(int(uid))
                t = time.perf_counter()
                res = eng.score(req)
                us = (time.perf_counter() - t) * 1e6
                cls = ("hot" if res.user_cache_hit
                       else "cold" if res.cold_hit else "recompute")
                lats[cls].append(us)
                if identity_engine is not None and len(identity) < identity_n:
                    identity.append((req, res.scores, cls))
        eng.flush_promotions()

        bit_identical = None
        if identity_engine is not None:
            bit_identical = True
            for req, scores, _ in identity:
                ref = identity_engine.score(req)
                if not np.array_equal(scores, ref.scores):
                    bit_identical = False
                    break

        n_hit = len(lats["hot"]) + len(lats["cold"])
        ms = eng.mem_stats()
        point = {
            "universe": universe,
            "requests": requests,
            "warmed": warmed,
            "warm_s": round(warm_s, 2),
            "capacity": ms["cold"]["capacity"],
            "cold_users": ms["cold"]["users"],
            "cold_bytes_used": ms["cold"]["bytes"],
            "hit_rate": round(n_hit / requests, 4),
            "demotions": ms["demotions"],
            "promotions": ms["promote"]["promotions"],
            "hot": _class_stats(lats["hot"]),
            "cold": _class_stats(lats["cold"]),
            "recompute": _class_stats(lats["recompute"]),
        }
        if bit_identical is not None:
            point["bit_identical"] = bit_identical
            point["identity_checked"] = len(identity)
            point["identity_classes"] = sorted({c for _, _, c in identity})
        return point
    finally:
        eng.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smallest universe only, short stream")
    ap.add_argument("--json", default=None, help="write payload here")
    ap.add_argument("--requests", type=int, default=None,
                    help="stream length per point (default 3000; smoke 600)")
    ap.add_argument("--candidates", type=int, default=128)
    ap.add_argument("--pool", type=int, default=64,
                    help="distinct user-feed tensors reused across the "
                         "universe (identity is per uid — see load.py)")
    ap.add_argument("--cold-bytes", type=int, default=None,
                    help="override the arena budget (default: MemPlan's)")
    ap.add_argument("--identity-n", type=int, default=48,
                    help="requests double-scored on a cache-off engine "
                         "for the bit-identity check (first point only)")
    args = ap.parse_args()

    universes = SMOKE_UNIVERSES if args.smoke else FULL_UNIVERSES
    requests = args.requests or (600 if args.smoke else 3000)

    graph, params = _build()
    # the bit-identity reference: no caches at all, every request is a
    # full recompute of the exact same executable family
    from repro.serve import ServePlan, ServingEngine
    ref = ServingEngine(graph, params, plan=ServePlan.preset("paper").evolve(
        cache__cache_user_reps=False, batch__linger_ms=0.0))

    rows = []
    points = {}
    try:
        for i, universe in enumerate(universes):
            t0 = time.perf_counter()
            point = run_point(
                graph, params, universe, requests, args.candidates,
                args.pool, args.cold_bytes, seed=i,
                identity_engine=ref if i == 0 else None,
                identity_n=args.identity_n)
            point["wall_s"] = round(time.perf_counter() - t0, 1)
            points[str(universe)] = point
            for cls in ("hot", "cold", "recompute"):
                st = point[cls]
                if st["p50_us"] is not None:
                    rows.append({"name": f"memtier/U{universe}/{cls}",
                                 "us_per_call": st["p50_us"],
                                 "derived": st["n"]})
            print(f"[memtier] U={universe}: hit_rate={point['hit_rate']} "
                  f"warmed={point['warmed']} "
                  f"hot={point['hot']['p50_us']}us "
                  f"cold={point['cold']['p50_us']}us "
                  f"recompute={point['recompute']['p50_us']}us "
                  f"({point['wall_s']}s)")
    finally:
        ref.close()

    payload = {
        "bench": "memtier",
        "smoke": bool(args.smoke),
        "config": {
            "zipf_s": ZIPF_S,
            "target_mass": TARGET_MASS,
            "requests": requests,
            "candidates": args.candidates,
            "pool": args.pool,
            "cold_bytes": args.cold_bytes,
            "max_cached_users": 4096,
            "device_slots": 256,
        },
        "rows": rows,
        "memtier": {"points": points},
    }
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"[memtier] wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
