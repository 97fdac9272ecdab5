"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. CPU wall-clock stands in for
the paper's GPU timings (speedup RATIOS are the reproduced quantity; the
dims are scaled by --scale to keep CPU runtimes sane — ratios are
dimension-homogeneous so scaling preserves them to first order).

  python -m benchmarks.run                 # all tables
  python -m benchmarks.run --bench table2  # one table
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.common import enable_compile_cache, timeit
from repro.core.mari import (mari_flops, matmul_mari, matmul_mari_fragmented,
                             matmul_vanilla, vanilla_flops)

_JSON_ROWS: list[dict] = []       # machine-readable mirror of the CSV rows
_JSON_EXTRA: dict = {}            # structured per-bench payloads (serve)


def _row(name: str, us: float, derived: str, plan=None, preset=None):
    """Emit one CSV row (+ JSON mirror). ``plan`` is the ``ServePlan`` that
    produced an engine-backed row — recorded verbatim in the JSON output so
    every bench row carries its exact serving config (provenance).
    ``preset`` labels the named preset the plan was derived from."""
    print(f"{name},{us:.1f},{derived}", flush=True)
    row = {"name": name, "us_per_call": round(us, 1), "derived": derived}
    if plan is not None:
        row["preset"] = preset if preset is not None else plan.preset_name()
        row["plan"] = plan.to_dict()
    _JSON_ROWS.append(row)


def _mk(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


def _time_pair(B, Du, Dr, d, iters=5):
    """Wall-time vanilla vs MaRI matmul at the given dims."""
    ks = jax.random.split(jax.random.PRNGKey(B + Du + Dr + d), 4)
    xu, xr = _mk(ks[0], 1, Du), _mk(ks[1], B, Dr)
    wu, wr = _mk(ks[2], Du, d), _mk(ks[3], Dr, d)
    x_tiled = jnp.concatenate([jnp.broadcast_to(xu, (B, Du)), xr], -1)
    w = jnp.concatenate([wu, wr], 0)
    f_van = jax.jit(matmul_vanilla)
    f_mari = jax.jit(matmul_mari)
    t_van = timeit(lambda: f_van(x_tiled, w), iters=iters)
    t_mari = timeit(lambda: f_mari(xu, xr, wu, wr), iters=iters)
    return t_van, t_mari


# ---------------------------------------------------------------------------
# Table 2 / Figure 3: MatMul_MaRI vs vanilla across B, D_user, D_rest, D_hid
# ---------------------------------------------------------------------------

def bench_table2(scale: float = 0.25, iters: int = 5):
    s = lambda x: max(16, int(x * scale))
    # varying B (D_user=4000, D_item=D_cross=1000, D_hidden=512)
    for B in [100, 500, 1000, 2000]:
        Du, Dr, d = s(4000), s(2000), s(512)
        tv, tm = _time_pair(B, Du, Dr, d, iters)
        fs = vanilla_flops(B, Du + Dr, d) / mari_flops(B, Du, Dr, d)
        _row(f"table2/varyB/B={B}", tm["mean_us"],
             f"time_speedup={tv['mean_us'] / tm['mean_us']:.2f}x;"
             f"flops_speedup={fs:.2f}x")
    # varying D_user (B=2000, D_rest=1000, D_hidden=512)
    for Du0 in [500, 1000, 2000, 4000, 8000]:
        B, Du, Dr, d = 2000, s(Du0), s(1000), s(512)
        tv, tm = _time_pair(B, Du, Dr, d, iters)
        fs = vanilla_flops(B, Du + Dr, d) / mari_flops(B, Du, Dr, d)
        _row(f"table2/varyDu/Du={Du0}", tm["mean_us"],
             f"time_speedup={tv['mean_us'] / tm['mean_us']:.2f}x;"
             f"flops_speedup={fs:.2f}x")
    # varying D_item/cross (B=2000, D_user=4000, D_hidden=512)
    for Dr0 in [500, 1000, 2000, 5000]:
        B, Du, Dr, d = 2000, s(4000), s(Dr0), s(512)
        tv, tm = _time_pair(B, Du, Dr, d, iters)
        fs = vanilla_flops(B, Du + Dr, d) / mari_flops(B, Du, Dr, d)
        _row(f"table2/varyDrest/Drest={Dr0}", tm["mean_us"],
             f"time_speedup={tv['mean_us'] / tm['mean_us']:.2f}x;"
             f"flops_speedup={fs:.2f}x")
    # varying D_hidden (B=2000, D_user=4000, D_item=1000)
    for d0 in [128, 512, 1024, 2048]:
        B, Du, Dr, d = 2000, s(4000), s(1000), s(d0)
        tv, tm = _time_pair(B, Du, Dr, d, iters)
        fs = vanilla_flops(B, Du + Dr, d) / mari_flops(B, Du, Dr, d)
        _row(f"table2/varyDhid/Dhid={d0}", tm["mean_us"],
             f"time_speedup={tv['mean_us'] / tm['mean_us']:.2f}x;"
             f"flops_speedup={fs:.2f}x")


# ---------------------------------------------------------------------------
# Table 3 / Figure 4: fragmented MaRI degradation vs chunk size (§2.4)
# ---------------------------------------------------------------------------

def bench_table3(scale: float = 0.25, iters: int = 5):
    B = 2000
    s = lambda x: max(16, int(x * scale))
    Du, Di, d = s(4000), s(1000), s(256)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xu, xi = _mk(ks[0], 1, Du), _mk(ks[1], B, Di)
    wu, wi = _mk(ks[2], Du, d), _mk(ks[3], Di, d)
    x_tiled = jnp.concatenate([jnp.broadcast_to(xu, (B, Du)), xi], -1)
    w = jnp.concatenate([wu, wi], 0)
    f_van = jax.jit(matmul_vanilla)
    f_neat = jax.jit(matmul_mari)
    t_van = timeit(lambda: f_van(x_tiled, w), iters=iters)["mean_us"]
    t_neat = timeit(lambda: f_neat(xu, xi, wu, wi), iters=iters)["mean_us"]
    _row("table3/original", t_van, "baseline=vanilla_matmul")
    _row("table3/neat_mari", t_neat,
         f"vs_original={100 * (t_neat - t_van) / t_van:+.1f}%")

    for chunk0 in [50, 100, 200, 400, 800]:
        chunk = max(4, int(chunk0 * scale))
        # interleave user/item chunks (the industrial fragmented layout)
        segs, off_u, off_i = [], 0, 0
        turn = 0
        while off_u < Du or off_i < Di:
            if (turn % 2 == 0 and off_u < Du) or off_i >= Di:
                wdt = min(chunk, Du - off_u)
                segs.append((xu[:, off_u:off_u + wdt],
                             wu[off_u:off_u + wdt]))
                off_u += wdt
            else:
                wdt = min(chunk, Di - off_i)
                segs.append((xi[:, off_i:off_i + wdt],
                             wi[off_i:off_i + wdt]))
                off_i += wdt
            turn += 1
        f_frag = jax.jit(lambda *flat: matmul_mari_fragmented(
            list(zip(flat[::2], flat[1::2]))))
        flat = [a for seg in segs for a in seg]
        t_frag = timeit(lambda: f_frag(*flat), iters=iters)["mean_us"]
        _row(f"table3/fragmented/chunk={chunk0}", t_frag,
             f"n_chunks={len(segs)};"
             f"vs_original={100 * (t_frag - t_van) / t_van:+.1f}%;"
             f"vs_neat={100 * (t_frag - t_neat) / t_neat:+.1f}%")


# ---------------------------------------------------------------------------
# Table 1: end-to-end ranking model — VanI vs UOI vs MaRI avg/p99
# ---------------------------------------------------------------------------

def bench_table1(iters: int = 30):
    from repro.core import apply_mari
    from repro.data.features import make_recsys_feeds
    from repro.graph.executor import Executor, init_graph_params
    from repro.models.ranking import (PaperRankingConfig,
                                      build_paper_ranking_model)

    cfg = PaperRankingConfig().scaled(0.12)
    graph, cfg = build_paper_ranking_model(cfg)
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    B = 2048
    feeds = make_recsys_feeds(graph, B, jax.random.PRNGKey(1))

    results = {}
    for mode in ("vani", "uoi", "mari"):
        if mode == "mari":
            g2, p2, _ = apply_mari(graph, params)
            step = jax.jit(Executor(g2, "uoi").run)
            args = (p2, feeds)
        else:
            step = jax.jit(Executor(graph, mode).run)
            args = (params, feeds)
        t = timeit(lambda: step(*args), warmup=3, iters=iters)
        results[mode] = t
        _row(f"table1/{mode}", t["mean_us"], f"p99_us={t['p99_us']:.1f}")
    avg = results["uoi"]["mean_us"] / results["mari"]["mean_us"]
    p99 = results["uoi"]["p99_us"] / results["mari"]["p99_us"]
    _row("table1/speedup_mari_vs_uoi", results["mari"]["mean_us"],
         f"avg={avg:.2f}x;p99={p99:.2f}x (paper: 1.32x/1.26x)")
    lat = 100 * (results["uoi"]["mean_us"] - results["mari"]["mean_us"]) \
        / results["uoi"]["mean_us"]
    _row("table1/stage_latency_change", results["mari"]["mean_us"],
         f"coarse_ranking_latency={-lat:.2f}% (paper: -2.24%)")


# ---------------------------------------------------------------------------
# Two-stage serving: vanilla/uoi/mari latency, cold vs user-cache-hit
# ---------------------------------------------------------------------------

def bench_serve(scale: float = 0.12, B: int = 2000, iters: int = 15,
                qps_users: int = 8, qps_passes: int = 9, qps_B: int = 256):
    """End-to-end ServingEngine latency + throughput on paper_ranking.

    Latency rows (per-request, candidate pool B):
      cold = new (user, feature_version) each request (stage 1 must run);
      hit  = repeat user (stage 1 skipped from the representation cache).
    Throughput rows (``serve/<mode>/qps``): a burst of ``qps_users``
    concurrent users, each with a ``qps_B``-candidate pool, scored
    sequentially (coalesce=off) vs through the async CoalescingBatcher
    (coalesce=on — cross-user chunks packed into shared stage-2 buckets).
    The two row families deliberately probe different regimes: latency
    rows use one big pool (B) that nearly fills ``max_batch`` by itself;
    qps rows use per-user pools small enough that several users' chunks
    share one stage-2 bucket — the cross-user batching the coalescer
    exists for (with pools ~= max_batch there is nothing to merge, only
    batcher overhead to pay).
    Breakdown rows (``serve/<mode>/breakdown``): the engine's per-phase
    stage profiler (pack/dispatch/device/unpack + stage1) over the latency
    loop, mean µs per phase per engine call.
    Emits CSV rows and a structured payload for --json.
    """
    import dataclasses

    import numpy as np
    from repro.data.features import make_recsys_feeds
    from repro.graph.executor import init_graph_params
    from repro.models.ranking import (PaperRankingConfig,
                                      build_paper_ranking_model)
    from repro.serve import (CoalescingBatcher, ServePlan, ServeRequest,
                             ServingEngine)

    cfg = PaperRankingConfig().scaled(scale)
    # Two-stage modes run the industrial regime the cache exists for: a
    # deep user tower (~140MB of stage-1 weights, ~10ms batch-1 on CPU)
    # that a cache hit skips entirely. vani keeps the thin tower — the
    # single-stage engine re-runs the user side across all B candidate
    # rows, so a deep tower there would measure nothing but GEMM time.
    heavy_cfg = dataclasses.replace(cfg,
                                    user_tower_widths=(4096, 4096, 4096))
    graphs = {}
    for name, c in (("thin", cfg), ("heavy", heavy_cfg)):
        g, _ = build_paper_ranking_model(c)
        graphs[name] = (g, init_graph_params(g, jax.random.PRNGKey(0)))
    graph = graphs["thin"][0]                  # identical inputs both graphs
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    feeds = make_recsys_feeds(graph, B, jax.random.PRNGKey(1))
    ufeeds = {k: v for k, v in feeds.items() if k in user_in}
    cand = {k: v for k, v in feeds.items() if k not in user_in}

    # rows are keyed by plan preset: each mode IS a preset's paradigm
    # (vanilla/uoi/paper), evolved with the bench's row budget. Two-stage
    # modes turn the device-resident rep tier on (the dispatch-overhead
    # fight this bench referees). The exact plan rides along in every JSON
    # row (provenance — incl. ``cache.device_resident``).
    presets = {"vani": "vanilla", "uoi": "uoi", "mari": "paper"}
    modes = {}
    for mode in ("vani", "uoi", "mari"):
        plan = ServePlan.preset(presets[mode]).evolve(batch__max_batch=4096)
        if mode != "vani":
            plan = plan.evolve(cache__device_resident=True)
        graph, params = graphs["thin" if mode == "vani" else "heavy"]
        eng = ServingEngine(graph, params, plan=plan)
        req = lambda uid, ver=0: ServeRequest(
            user_id=uid, user_feeds=ufeeds, candidate_feeds=cand,
            feature_version=ver)
        eng.score(req(-1))                      # compile both stages
        eng.score(req(0))                       # warm user 0's rep cache
        # the latency-contract asserts that used to live here (vani hit ≤
        # 1.25× cold) moved to benchmarks/check_serve_trend.py — the CI
        # trend gate owns ALL latency contracts now, against both the
        # committed baseline and the fresh rows.
        # atomic snapshot+reset: discards the warmup phases in one lock
        # acquisition, so the breakdown covers exactly the timed loop
        eng.profiler.snapshot(reset=True)
        cold, hit = [], []
        for it in range(iters):
            cold.append(eng.score(req(it + 1, ver=it)).latency_ms)
            hit.append(eng.score(req(0)).latency_ms)
        cold_ms = float(np.median(cold))
        hit_ms = float(np.median(hit))
        breakdown = eng.profiler.snapshot()
        modes[mode] = {
            "cold_ms": round(cold_ms, 3), "hit_ms": round(hit_ms, 3),
            "two_stage": eng.two_stage,
            "device_resident": eng.device_resident,
            "stage2_compilations": eng.stage2_compilations,
            "breakdown": breakdown,
            "preset": presets[mode],
            "plan": plan.to_dict(),
        }
        _row(f"serve/{mode}/cold", cold_ms * 1e3,
             f"B={B};two_stage={eng.two_stage};preset={presets[mode]}",
             plan=plan, preset=presets[mode])
        _row(f"serve/{mode}/hit", hit_ms * 1e3,
             f"B={B};hit_speedup={cold_ms / hit_ms:.2f}x",
             plan=plan, preset=presets[mode])
        # per-phase dispatch-path breakdown: mean µs per engine call of
        # each hot-path phase over the latency loop (us_per_call = their
        # sum, i.e. profiled wall per call minus unprofiled slack)
        phase_us = {p: breakdown[p]["mean_us"]
                    for p in ("pack", "dispatch", "device", "unpack")}
        _row(f"serve/{mode}/breakdown", sum(phase_us.values()),
             ";".join(f"{p}={u:.1f}us" for p, u in phase_us.items())
             + f";stage1={breakdown['stage1']['mean_us']:.1f}us"
             + f";device_resident={eng.device_resident}",
             plan=plan, preset=presets[mode])

        # -- throughput: cross-user coalescing on vs off. Passes are
        # interleaved (off, on, off, on, ...) so machine-load drift lands on
        # both sides instead of whichever ran second; medians per side. ----
        import time as _time
        candq = {k: v[:qps_B] for k, v in cand.items()}
        reqq = lambda uid: ServeRequest(
            user_id=uid, user_feeds=ufeeds, candidate_feeds=candq)
        burst = [reqq(uid) for uid in range(qps_users)]
        for r in burst:                         # warm every user's rep cache
            eng.score(r)
        seq_ref = [eng.score(r) for r in burst]
        walls_off, walls_on = [], []
        with CoalescingBatcher(eng, linger_ms=1.0) as batcher:
            co_ref = batcher.score_many(burst)  # compile coalesced shapes
            # window the latency histograms to the timed passes: a compile
            # landing in an 80-sample p99 would pin the latency_p99 row
            # below to compile-time noise
            batcher.request_latency.reset()
            batcher.queue_wait.reset()
            for _ in range(qps_passes):
                t0 = _time.perf_counter()
                for r in burst:
                    eng.score(r)
                walls_off.append(_time.perf_counter() - t0)
                t0 = _time.perf_counter()
                batcher.score_many(burst)
                walls_on.append(_time.perf_counter() - t0)
        qps_off = qps_users / float(np.median(walls_off))
        qps_on = qps_users / float(np.median(walls_on))
        for s, c in zip(seq_ref, co_ref):       # lossless sanity
            assert np.array_equal(s.scores, c.scores), \
                "coalescing changed scores"
        modes[mode]["qps"] = {
            "coalesce_off": round(qps_off, 1), "coalesce_on": round(qps_on, 1),
            "users": qps_users, "B": qps_B,
            "speedup": round(qps_on / qps_off, 3),
        }
        _row(f"serve/{mode}/qps/coalesce=off", 1e6 / qps_off,
             f"B={qps_B};users={qps_users};qps={qps_off:.1f}",
             plan=plan, preset=presets[mode])
        _row(f"serve/{mode}/qps/coalesce=on", 1e6 / qps_on,
             f"B={qps_B};users={qps_users};qps={qps_on:.1f};"
             f"vs_off={qps_on / qps_off:.2f}x",
             plan=plan, preset=presets[mode])

        # -- latency distribution (repro.obs histograms): every request the
        # qps loop pushed through the batcher, p50/p99 without retaining
        # samples — the same numbers RankingService.stats() reports. ------
        lat_snap = batcher.request_latency.snapshot()
        qw_snap = batcher.queue_wait.snapshot()
        modes[mode]["latency"] = {"request_ms": lat_snap,
                                  "queue_wait_ms": qw_snap}
        _row(f"serve/{mode}/latency_p50", lat_snap["p50"] * 1e3,
             f"B={qps_B};n={lat_snap['count']};p90={lat_snap['p90']:.2f}ms",
             plan=plan, preset=presets[mode])
        _row(f"serve/{mode}/latency_p99", lat_snap["p99"] * 1e3,
             f"B={qps_B};queue_wait_p99={qw_snap['p99']:.3f}ms",
             plan=plan, preset=presets[mode])

        # -- observability overhead (mari only): the SAME burst through a
        # second engine built with ObsPlan.trace on, passes interleaved
        # with a plain engine so machine drift lands on both sides. The
        # trend gate bounds the ratio: tracing must stay cheap enough to
        # leave on under load. ---------------------------------------------
        if mode == "mari":
            obs_eng = ServingEngine(graph, params,
                                    plan=plan.evolve(obs__trace=True))
            for r in burst:
                obs_eng.score(r)
            w_off, w_obs = [], []
            with CoalescingBatcher(eng, linger_ms=1.0) as b_off, \
                    CoalescingBatcher(obs_eng, linger_ms=1.0) as b_on:
                b_off.score_many(burst)         # warm both batchers
                b_on.score_many(burst)
                for _ in range(qps_passes):
                    t0 = _time.perf_counter()
                    b_off.score_many(burst)
                    w_off.append(_time.perf_counter() - t0)
                    t0 = _time.perf_counter()
                    b_on.score_many(burst)
                    w_obs.append(_time.perf_counter() - t0)
            qps_plain = qps_users / float(np.median(w_off))
            qps_obs = qps_users / float(np.median(w_obs))
            modes[mode]["obs"] = {
                "qps_trace_off": round(qps_plain, 1),
                "qps_trace_on": round(qps_obs, 1),
                "ratio": round(qps_obs / qps_plain, 3),
                "events": len(obs_eng.tracer),
            }
            _row(f"serve/{mode}/qps/trace=on", 1e6 / qps_obs,
                 f"B={qps_B};users={qps_users};qps={qps_obs:.1f};"
                 f"vs_trace_off={qps_obs / qps_plain:.2f}x;"
                 f"events={len(obs_eng.tracer)}",
                 plan=plan, preset=presets[mode])
            obs_eng.close()
        eng.close()
    _JSON_EXTRA["serve"] = {"config": "paper_ranking", "scale": scale,
                            "B": B, "iters": iters, "modes": modes}


# ---------------------------------------------------------------------------
# Distributed serving: shards-vs-qps (single- and multi-process stage 2)
# ---------------------------------------------------------------------------

def bench_dist(shards=(1, 2, 4), pool: int = 2000, users: int = 4,
               passes: int = 5, scale: float = 0.05, modes: str = "mari",
               two_process: bool = True):
    """Candidate-axis sharded stage 2 at increasing shard counts — a
    CPU-only rehearsal of the sharding path, not a device measurement.

    Each row runs in a subprocess (``repro.dist.runner``) so every shard
    count gets its own forced host-device world; the final row exercises
    the REAL multi-process path (2 ``jax.distributed`` workers). On one
    physical CPU the forced devices share cores, so qps-vs-shards mostly
    reports sharding overhead, not speedup — the row the trajectory
    tracks is that overhead staying flat. Scores per run are verified
    against the float32 reference within the stated tolerance (--verify).

    CPU only: this process has already touched JAX, so on a chip host it
    holds the chips and its children could not reach them.
    """
    import os
    import subprocess
    import sys

    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"--bench dist is a CPU-only rehearsal (forced host devices in "
            f"child processes); this process runs on "
            f"{jax.default_backend()!r} and holds its devices — run it "
            f"with JAX_PLATFORMS=cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")

    def run(n_proc: int, dev_per_proc: int) -> list[dict]:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "repro.dist.runner",
               "--spawn", str(n_proc),
               "--devices-per-process", str(dev_per_proc),
               "--bench", "--verify", "--modes", modes,
               "--pool", str(pool), "--users", str(users),
               "--passes", str(passes), "--scale", str(scale),
               "--max-batch", "1024", "--min-bucket", "128"]
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=900)
        if p.returncode != 0:
            raise RuntimeError(f"dist bench worker failed:\n{p.stderr[-2000:]}")
        return [json.loads(line) for line in p.stdout.strip().splitlines()
                if line.startswith("{") and "qps" in line]

    def breakdown_row(prefix: str, r: dict) -> None:
        # sibling row to serve/<mode>/breakdown: per-phase mean µs per
        # engine call over the worker's timed passes, so per-shard qps
        # stays attributable to pack/dispatch/device/unpack
        bd = r.get("breakdown")
        if not bd:
            return
        phase_us = {p: bd[p]["mean_us"]
                    for p in ("pack", "dispatch", "device", "unpack")}
        _row(f"{prefix}/breakdown", sum(phase_us.values()),
             ";".join(f"{p}={u:.1f}us" for p, u in phase_us.items())
             + f";stage1={bd['stage1']['mean_us']:.1f}us")

    records = []
    for n in shards:
        for r in run(1, n):
            records.append(r)
            name = f"dist/{r['mode']}/shards={r['shards']}"
            _row(name, 1e6 / r["qps"],
                 f"procs=1;pool={r['pool']};users={r['users']};"
                 f"qps={r['qps']};within_tol={r.get('within_tol')}")
            breakdown_row(name, r)
    if two_process:
        nproc_dev = max(max(shards) // 2, 1)
        for r in run(2, nproc_dev):
            records.append(r)
            name = f"dist/{r['mode']}/shards={r['shards']}/procs=2"
            _row(name, 1e6 / r["qps"],
                 f"procs=2;pool={r['pool']};users={r['users']};"
                 f"qps={r['qps']};within_tol={r.get('within_tol')}")
            breakdown_row(name, r)
    _JSON_EXTRA["dist"] = {"config": "paper_ranking", "scale": scale,
                           "pool": pool, "users": users, "passes": passes,
                           "records": records}


# ---------------------------------------------------------------------------
# Gather-aware attention: stage-2 peak memory + latency, gather on vs off
# ---------------------------------------------------------------------------

def bench_attn(B: int = 2000, users: int = 8, iters: int = 5):
    """Reparam-DIN stage 2 with the attention-side gather fused vs
    materialized.

    Both engines run the identical row-wise executable family on a
    ``users``-slot rep table and a B-candidate coalesced batch (Pallas in
    interpret mode on CPU — wall-clock is interpreter-dominated; the row
    the trajectory tracks is ``peak_bytes``). gather=off gathers the
    boundary ``T``/``u_part``/keys tables to row-wise blocks — peak temp
    memory carries the (B, L, D, h) tensor — while gather=on indexes the
    stacked tables inside ``kernels.gather_einsum``, so peak memory scales
    with U·L·D·h + B·d instead of B·L·D·h. Peak bytes come from
    ``jit(...).lower().compile().memory_analysis()`` on the actual stage-2
    executable.
    """
    import numpy as np
    from repro.common import next_pow2
    from repro.data.features import make_recsys_feeds
    from repro.graph.executor import init_graph_params
    from repro.models.recsys import build_din
    from repro.serve import ServePlan, ServeRequest, ServingEngine

    graph, _ = build_din(embed_dim=8, seq_len=24, attn_mlp=(16, 8),
                         mlp=(24, 12), item_vocab=4096)
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    bucket = next_pow2(B)
    cand = {k: v for k, v in
            make_recsys_feeds(graph, bucket, jax.random.PRNGKey(99)).items()
            if k not in user_in}
    # engine-identical index layout: contiguous user slots, padded tail rows
    # reuse the last real slot
    uidx = np.full((bucket,), users - 1, np.int32)
    uidx[:B] = np.repeat(np.arange(users), -(-B // users))[:B]
    uidx = jnp.asarray(uidx)

    results = {}
    outs = {}
    plans = {}
    for gather in (False, True):
        plans[gather] = ServePlan.preset("tpu").evolve(
            kernel__kernel_gather=False, kernel__gather_attention=gather,
            batch__max_batch=4096)
        eng = ServingEngine(graph, params, plan=plans[gather])
        reps = []
        for uid in range(users):
            feeds = make_recsys_feeds(graph, 1, jax.random.PRNGKey(uid + 1))
            reps.append(eng._user_reps(ServeRequest(
                uid, {k: v for k, v in feeds.items() if k in user_in},
                {}), eng.new_group_id())[0])
        table = {k: jnp.concatenate([r[k] for r in reps], axis=0)
                 for k in reps[0]}
        # AOT-compile once and reuse the executable for memory stats,
        # timing, AND outputs (calling eng._stage2 again would re-trace and
        # re-compile — jit's dispatch cache doesn't see the AOT result)
        compiled = eng._stage2.lower(eng._params_s2, table, uidx,
                                     cand).compile()
        try:
            peak = int(compiled.memory_analysis().temp_size_in_bytes)
        except Exception:       # backend without buffer stats
            peak = -1
        t = timeit(lambda: compiled(eng._params_s2, table, uidx, cand),
                   warmup=1, iters=iters)
        outs[gather] = np.concatenate(
            [np.asarray(v) for v in compiled(
                eng._params_s2, table, uidx, cand).values()], axis=-1)
        results[gather] = {"us_per_call": round(t["mean_us"], 1),
                           "peak_bytes": peak}
        eng.close()
    # the two memory profiles must score identically
    assert np.allclose(outs[False], outs[True], rtol=1e-5, atol=1e-5), \
        "gather-aware attention changed scores"
    off_peak = results[False]["peak_bytes"]
    on_peak = results[True]["peak_bytes"]
    # ratio is None (JSON null) when the backend reported no buffer stats —
    # a NaN would serialize as invalid JSON and -1 would fake a win
    ratio = on_peak / off_peak if off_peak > 0 and on_peak >= 0 else None
    if ratio is not None:
        # THE contract this bench guards: gather-on stage-2 peak live bytes
        # must not scale with B*L*D*h (<= 0.5x the materializing path)
        assert ratio <= 0.5, (
            f"gather-on peak {on_peak}B > 0.5x gather-off {off_peak}B — "
            f"the attention gather is materializing again")
    for gather in (False, True):
        r = results[gather]
        _row(f"attn/din_reparam/gather={'on' if gather else 'off'}",
             r["us_per_call"],
             f"B={B};users={users};bucket={bucket};"
             f"peak_bytes={r['peak_bytes']}"
             + (f";peak_ratio={ratio:.3f}x"
                if gather and ratio is not None else ""),
             plan=plans[gather])
        results[gather]["plan"] = plans[gather].to_dict()
    _JSON_EXTRA["attn"] = {"config": "din_reparam", "B": B, "users": users,
                           "bucket": bucket,
                           "gather_off": results[False],
                           "gather_on": results[True],
                           "peak_ratio": (round(ratio, 4)
                                          if ratio is not None else None)}


# ---------------------------------------------------------------------------
# Appendix B.1: UOI vs VanI cross-attention (K/V projected once vs B times)
# ---------------------------------------------------------------------------

def bench_uoi_attention(iters: int = 10):
    from repro.nn.attention import cross_attention
    d, L = 64, 256
    for B in [128, 512, 2048]:
        ks = jax.random.split(jax.random.PRNGKey(B), 3)
        q = _mk(ks[0], B, 1, d)
        k1 = _mk(ks[1], 1, L, d)
        v1 = _mk(ks[2], 1, L, d)
        kB = jnp.broadcast_to(k1, (B, L, d)) + 0.0   # materialized tile
        vB = jnp.broadcast_to(v1, (B, L, d)) + 0.0
        wk, wv = _mk(ks[0], d, d), _mk(ks[1], d, d)

        @jax.jit
        def attn(q, k, v):
            return cross_attention(q, k @ wk, v @ wv)

        tv = timeit(lambda: attn(q, kB, vB), iters=iters)["mean_us"]
        tu = timeit(lambda: attn(q, k1, v1), iters=iters)["mean_us"]
        flops_ratio = (B + 2 * L) / (B * (1 + 2 * L))
        _row(f"appendixB1/uoi_vs_vani/B={B}", tu,
             f"time_speedup={tv / tu:.2f}x;flops_ratio={flops_ratio:.4f}")


BENCHES = {
    "table1": bench_table1,
    "table2": bench_table2,
    "table3": bench_table3,
    "serve": bench_serve,
    "dist": bench_dist,
    "attn": bench_attn,
    "uoi": bench_uoi_attention,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", choices=list(BENCHES) + ["all"], default="all")
    ap.add_argument("--scale", type=float, default=0.25,
                    help="dimension scale for CPU-feasible timings")
    ap.add_argument("--serve-scale", type=float, default=0.12,
                    help="paper_ranking scale for the serve bench (kept "
                         "separate: the serve bench times a full engine, not "
                         "one matmul)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write machine-readable results (e.g. "
                         "BENCH_serve.json) for perf-trajectory tracking")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.bench in ("table2", "all"):
        bench_table2(args.scale)
    if args.bench in ("table3", "all"):
        bench_table3(args.scale)
    if args.bench in ("table1", "all"):
        bench_table1()
    if args.bench in ("serve", "all"):
        bench_serve(args.serve_scale)
    if args.bench == "dist":
        # not in "all": forced-device subprocess worlds are heavyweight and
        # CI runs this as its own artifact step (BENCH_dist.json)
        bench_dist()
    if args.bench == "attn":
        # not in "all": interpret-mode Pallas at a 2048-row bucket is slow
        # on CPU; CI runs this as its own artifact step (BENCH_attn.json)
        bench_attn()
    if args.bench in ("uoi", "all"):
        bench_uoi_attention()
    if args.json:
        payload = {"bench": args.bench, "rows": _JSON_ROWS, **_JSON_EXTRA}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
