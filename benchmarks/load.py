"""Closed-loop Zipf load harness for the serving stack.

Drives the real ``RankingService`` (engine + continuous-dispatch
``CoalescingBatcher`` + admission control, all wired ``from_plan``) with
the workload shape the batcher exists for: a large user universe under a
Zipf popularity law (a hot head that lives in the rep caches, a cold tail
that pays stage 1), Poisson open-loop arrivals at swept offered loads,
and a deadline-class slice riding the priority queue.

Per preset it runs TWO variants of the same plan — ``continuous`` (the
two-phase overlapped dispatch loop) and ``lockstep``
(``batch.continuous=False``) — and reports, per offered-load point:
achieved qps, p50/p95/p99 latency, and the admission counters
(shed/degrade, by SLO class). Saturation qps per variant comes from a
closed-loop probe (``--workers`` synchronous submitters, no pacing).
The two curves answer the PR's question directly: does overlapping
group k+1's host work under group k's device time buy tail latency and
saturation throughput, at identical offered load and identical scores?

  python -m benchmarks.load --json BENCH_load.json          # full curves
  python -m benchmarks.load --smoke --json BENCH_smoke.json # CI gate
  python -m benchmarks.load --check --json BENCH_load.json  # + acceptance

``--smoke`` shrinks the universe/durations and asserts the harness
contracts (achieved tracks offered at low load, curve monotone-ish,
deadline class never shed at low load). ``--check`` additionally asserts
the PR's acceptance: continuous p99 <= lockstep p99 at the fixed
sub-saturation point and continuous saturation >= lockstep (within
``--tol`` measurement slack on this shared-CPU box).

``--chaos`` switches the harness into the self-healing acceptance run:
one scenario served under a deterministic fault schedule (device-tier
write faults, stage-2 dispatch faults, injected result corruption, and
one worker-thread kill), asserting that every submitted future resolves
(zero hung), every SUCCESSFUL response is bit-identical to a fault-free
reference, availability stays above ``--chaos-floor``, and the circuit
breaker demonstrably restores the device-resident fast path
(open -> ... -> closed, via ``RankingService.stats()`` counters).
"""
from __future__ import annotations

import argparse
import gc
import json
import threading
import time
from concurrent.futures import wait as _wait_futures
from contextlib import contextmanager

import numpy as np

VARIANTS = ("continuous", "lockstep")


@contextmanager
def _quiesced_gc():
    """Collect before, disable during, re-enable after a timed segment —
    a CPython GC pause mid-window is tens of ms of phantom tail latency
    attributed to whichever variant happened to be measuring."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


# ---------------------------------------------------------------------------
# Zipf user universe
# ---------------------------------------------------------------------------

def zipf_cdf(universe: int, s: float) -> np.ndarray:
    """CDF of a bounded Zipf(s) law over user ids 0..universe-1 (id = rank:
    small ids are the hot head)."""
    w = 1.0 / np.arange(1, universe + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return cdf


def sample_users(cdf: np.ndarray, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    return np.searchsorted(cdf, rng.random(n), side="left").astype(np.int64)


# ---------------------------------------------------------------------------
# Workload: one service, two scenarios per preset (continuous / lockstep)
# ---------------------------------------------------------------------------

class Workload:
    """Request factory over a Zipf universe.

    ``pool`` distinct user-feed tensors are reused across the universe
    (uid -> pool slot uid % pool): feature VALUES repeat, but every uid is
    its own cache/device-slot identity — what the rep tier actually keys
    on — so cache-hit and slot-recycling behavior is that of ``universe``
    users at the memory cost of ``pool``.
    """

    def __init__(self, graph, B: int, pool: int, seed: int = 0):
        from repro.data.features import make_recsys_feeds
        self.B = B
        user_in = {n.name for n in graph.input_nodes()
                   if n.attrs.get("domain") == "user"}
        import jax
        feeds = make_recsys_feeds(graph, 1024, jax.random.PRNGKey(seed + 1))
        self.cand_full = {k: v for k, v in feeds.items() if k not in user_in}
        self.cand = {k: v[:B] for k, v in self.cand_full.items()}
        self.ufeeds = []
        for i in range(pool):
            f = make_recsys_feeds(graph, 1, jax.random.PRNGKey(seed + 100 + i))
            self.ufeeds.append({k: v for k, v in f.items() if k in user_in})

    def req(self, uid: int, rows: int | None = None):
        from repro.serve import ServeRequest
        cand = (self.cand if rows is None
                else {k: v[:rows] for k, v in self.cand_full.items()})
        return ServeRequest(user_id=int(uid),
                            user_feeds=self.ufeeds[uid % len(self.ufeeds)],
                            candidate_feeds=cand)


def build_plan(preset: str, variant: str, args):
    """One serving plan per (preset, variant): identical engine shape, only
    ``batch.continuous`` differs — the comparison isolates the loop."""
    from repro.serve import ServePlan
    plan = ServePlan.preset(preset).evolve(
        batch__max_batch=args.max_batch, batch__min_bucket=args.B,
        batch__linger_ms=args.linger_ms, batch__admission=True,
        batch__shed_queue_depth=args.shed_depth,
        batch__degrade_queue_depth=args.degrade_depth,
        batch__degrade_frac=0.5, batch__deadline_headroom_ms=0.25,
        cache__device_resident=True, cache__device_slots=args.device_slots)
    if variant == "lockstep":
        plan = plan.evolve(batch__continuous=False)
    if getattr(args, "trace", None):
        # ring-buffer tracing: bounded memory even over long sweeps, and
        # the retained window is the newest (most loaded) segment
        plan = plan.evolve(obs__trace=True)
    return plan


def warm(svc, scenario: str, wl: Workload, max_batch: int) -> None:
    """Compile every stage-2 bucket the run can touch (pow2 sizes from B up
    to max_batch; degraded pools land back in the B bucket via min_bucket)
    and the coalesced path, so no compile lands inside a timed point."""
    rows = wl.B
    while rows <= max_batch:
        svc.score(scenario, wl.req(0, rows=rows))
        rows *= 2
    svc.score_many([(scenario, wl.req(1)), (scenario, wl.req(2)),
                    (scenario, wl.req(3))])
    # compile the copy-on-write table writer too: a cold user arriving
    # while a launch is in flight forks the table generation, and that
    # path must not pay its jit compile inside a timed window. Driven
    # through the two-phase API directly (the batcher is idle here).
    eng = svc.engine(scenario)
    if getattr(eng, "device_store", None) is not None \
            and hasattr(eng, "begin_coalesced"):
        h1 = eng.begin_coalesced([wl.req(10_000_019)])
        h2 = eng.begin_coalesced([wl.req(10_000_033)])  # cold under flight
        eng.collect(h1)
        eng.collect(h2)


# ---------------------------------------------------------------------------
# Load loops
# ---------------------------------------------------------------------------

def _counters(svc, scenario: str) -> dict:
    s = svc.stats()["scenarios"][scenario]
    return {k: s[k] for k in ("shed_best_effort", "shed_deadline",
                              "degraded_requests", "pipeline_forks")}


def closed_loop_saturation(svc, scenario: str, wl: Workload,
                           ring: np.ndarray, duration: float,
                           workers: int) -> dict:
    """Max sustainable throughput: ``workers`` synchronous submitters with
    zero think time — the queue always holds ~``workers`` requests, so the
    dispatch loop is never starved and never admission-limited
    (``workers`` < degrade threshold)."""
    from repro.serve import SLO_BEST_EFFORT, AdmissionError
    stop_at = time.perf_counter() + duration
    lock = threading.Lock()
    done = [0]
    lats: list[float] = []

    def run(wid: int) -> None:
        i = wid * 7919          # decorrelate the per-thread uid streams
        local: list[float] = []
        n = 0
        while time.perf_counter() < stop_at:
            uid = int(ring[i % len(ring)])
            i += 1
            t0 = time.perf_counter()
            try:
                svc.submit(scenario, wl.req(uid),
                           slo=SLO_BEST_EFFORT).result()
            except AdmissionError:
                continue
            local.append((time.perf_counter() - t0) * 1e3)
            n += 1
        with lock:
            done[0] += n
            lats.extend(local)

    with _quiesced_gc():
        t_start = time.perf_counter()
        threads = [threading.Thread(target=run, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_start
    return {"qps": round(done[0] / elapsed, 1), "completed": done[0],
            "workers": workers, "duration_s": round(elapsed, 3),
            "p50_ms": round(float(np.percentile(lats, 50)), 3) if lats else None}


def open_loop_segment(svc, scenario: str, wl: Workload, ring: np.ndarray,
                      offered_qps: float, duration: float,
                      rng: np.random.Generator, deadline_frac: float,
                      deadline_ms: float, phase: int = 0) -> dict:
    """One measurement segment: Poisson arrivals at ``offered_qps`` for
    ``duration`` seconds, a ``deadline_frac`` slice submitted with the
    deadline SLO. Latency is submit-to-future-resolution (queue wait
    included — the number an upstream caller sees). Segments are short so
    the two variants can interleave them and sample the same machine-noise
    distribution; ``aggregate_point`` merges a variant's segments."""
    from repro.serve import SLO_BEST_EFFORT, SLO_DEADLINE
    lock = threading.Lock()
    recs: list[tuple[float, float, bool]] = []

    def cb(fut, t0: float) -> None:
        t1 = time.perf_counter()
        with lock:
            recs.append((t0, t1, fut.exception() is None))

    before = _counters(svc, scenario)
    futs = []
    submitted = 0
    i = phase * 7919            # decorrelate uid streams across segments
    with _quiesced_gc():
        t_start = time.perf_counter()
        t_end = t_start + duration
        next_t = t_start
        while True:
            next_t += rng.exponential(1.0 / offered_qps)
            if next_t >= t_end:
                break
            lag = next_t - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            uid = int(ring[i % len(ring)])
            i += 1
            dl = deadline_ms if rng.random() < deadline_frac else None
            t0 = time.perf_counter()
            fut = svc.submit(scenario, wl.req(uid),
                             slo=SLO_DEADLINE if dl is not None
                             else SLO_BEST_EFFORT,
                             deadline_ms=dl)
            fut.add_done_callback(lambda f, t0=t0: cb(f, t0))
            futs.append(fut)
            submitted += 1
        _wait_futures(futs, timeout=120.0)
    after = _counters(svc, scenario)
    return {
        "lat_ms": [(t1 - t0) * 1e3 for t0, t1, ok in recs if ok],
        "submitted": submitted, "duration": duration,
        "shed_best_effort": after["shed_best_effort"]
        - before["shed_best_effort"],
        "shed_deadline": after["shed_deadline"] - before["shed_deadline"],
        "degraded": after["degraded_requests"] - before["degraded_requests"],
    }


def aggregate_point(segs: list[dict], offered_qps: float,
                    deadline_frac: float) -> dict:
    lat = sorted(x for s in segs for x in s["lat_ms"])
    total_dur = sum(s["duration"] for s in segs)
    submitted = sum(s["submitted"] for s in segs)
    shed_be = sum(s["shed_best_effort"] for s in segs)
    shed_dl = sum(s["shed_deadline"] for s in segs)
    pct = (lambda q: round(float(np.percentile(lat, q)), 3)) if lat \
        else (lambda q: None)
    return {
        "offered_qps": round(offered_qps, 1),
        "achieved_qps": round(len(lat) / total_dur, 1),
        "submitted": submitted, "completed": len(lat),
        "segments": len(segs),
        "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
        "shed_best_effort": shed_be, "shed_deadline": shed_dl,
        "degraded": sum(s["degraded"] for s in segs),
        "shed_rate": round((shed_be + shed_dl) / max(submitted, 1), 4),
        "deadline_frac": deadline_frac,
    }


# ---------------------------------------------------------------------------
# Per-preset run: saturation probe + offered-load curve, both variants
# ---------------------------------------------------------------------------

def run_preset(svc, preset: str, wl: Workload, ring: np.ndarray,
               args, rng: np.random.Generator) -> dict:
    """Saturation probes and curve points run as short segments with the
    two variants strictly interleaved (c,l,c,l,...) — slow machine-noise
    drift on this shared CPU lands on both sides instead of whichever
    variant happened to run second; per-variant stats merge segments."""
    scen = {v: f"{preset}:{v}" for v in VARIANTS}
    variants: dict = {v: {"curve": []} for v in VARIANTS}
    sat_probes: dict = {v: [] for v in VARIANTS}
    for _ in range(args.reps):
        for v in VARIANTS:
            sat_probes[v].append(closed_loop_saturation(
                svc, scen[v], wl, ring, args.duration, args.workers))
    for v in VARIANTS:
        qps = round(float(np.median([p["qps"] for p in sat_probes[v]])), 1)
        variants[v]["saturation"] = {"qps": qps, "probes": sat_probes[v],
                                     "workers": args.workers}
        print(f"load/{preset}/{v}/saturation,qps={qps},"
              f"workers={args.workers}", flush=True)
    # both variants face the SAME absolute offered loads — fractions of the
    # weaker variant's saturation, so every sub-1.0 point is sub-saturation
    # for both and the comparison is at fixed load
    base_qps = min(variants[v]["saturation"]["qps"] for v in VARIANTS)
    for frac in args.fractions:
        offered = max(frac * base_qps, 1.0)
        segs: dict = {v: [] for v in VARIANTS}
        for rep in range(args.reps):
            for v in VARIANTS:
                segs[v].append(open_loop_segment(
                    svc, scen[v], wl, ring, offered_qps=offered,
                    duration=args.duration, rng=rng,
                    deadline_frac=args.deadline_frac,
                    deadline_ms=args.deadline_ms, phase=rep))
        for v in VARIANTS:
            pt = aggregate_point(segs[v], offered, args.deadline_frac)
            pt["fraction_of_saturation"] = frac
            variants[v]["curve"].append(pt)
            print(f"load/{preset}/{v}/offered={frac:g}x,"
                  f"qps={pt['achieved_qps']},p99_ms={pt['p99_ms']},"
                  f"shed={pt['shed_best_effort'] + pt['shed_deadline']},"
                  f"degraded={pt['degraded']}", flush=True)
    for v in VARIANTS:
        variants[v]["pipeline_forks"] = \
            _counters(svc, scen[v])["pipeline_forks"]
    # comparison at the largest CLEARLY sub-saturation fraction: near 1.0
    # the queue rides the edge of instability and tiny service-rate
    # deltas integrate into unbounded waiting-time noise
    sub = max((f for f in args.fractions if f <= 0.75),
              default=min(args.fractions))
    idx = args.fractions.index(sub)
    cpt = variants["continuous"]["curve"][idx]
    lpt = variants["lockstep"]["curve"][idx]
    comparison = {
        "base_qps": base_qps,
        "sub_saturation_fraction": sub,
        "offered_qps": cpt["offered_qps"],
        "continuous_p99_ms": cpt["p99_ms"],
        "lockstep_p99_ms": lpt["p99_ms"],
        "p99_ratio": (round(cpt["p99_ms"] / lpt["p99_ms"], 3)
                      if cpt["p99_ms"] and lpt["p99_ms"] else None),
        "continuous_saturation_qps": variants["continuous"]["saturation"]["qps"],
        "lockstep_saturation_qps": variants["lockstep"]["saturation"]["qps"],
        "saturation_ratio": round(
            variants["continuous"]["saturation"]["qps"]
            / variants["lockstep"]["saturation"]["qps"], 3),
    }
    print(f"load/{preset}/comparison,p99_ratio={comparison['p99_ratio']},"
          f"saturation_ratio={comparison['saturation_ratio']}", flush=True)
    return {"variants": variants, "comparison": comparison}


# ---------------------------------------------------------------------------
# Chaos: deterministic fault schedule + self-healing acceptance
# ---------------------------------------------------------------------------

# Count-bounded (p=1) specs land on the same pokes every run: 3 of the 4
# slot_write faults quarantine the device tier and open the breaker
# (breaker_failures=3); dispatch faults and injected corruption exercise
# the retry path mid-stream; one worker_loop fault kills the dispatch
# thread once. Every count is finite, so the recovery phase always
# reaches a clean half-open probe and the breaker closes.
CHAOS_SITES = (
    "slot_write:error:count=4",
    "stage2_dispatch:error:after=10,count=3",
    "collect:corrupt:after=6,count=2",
    "worker_loop:error:after=4,count=1",
)


def build_chaos_plan(args):
    from repro.serve import ServePlan
    plan = ServePlan.preset("paper").evolve(
        batch__max_batch=args.max_batch, batch__min_bucket=args.B,
        batch__linger_ms=args.linger_ms, cache__device_resident=True,
        cache__device_slots=args.device_slots,
        ft__inject=True, ft__seed=args.seed, ft__sites=CHAOS_SITES,
        ft__retries=4, ft__retry_backoff_ms=2.0,
        ft__breaker_failures=3, ft__breaker_cooldown_ms=150.0,
        ft__breaker_probes=1)
    if getattr(args, "trace", None):
        plan = plan.evolve(obs__trace=True)
    return plan


def run_chaos(svc, graph, params, wl: Workload, args) -> dict:
    """Drive one scenario under ``CHAOS_SITES`` and assert self-healing.

    Contract (the PR's acceptance): zero hung futures, bit-identical
    scores on every success vs a fault-free reference, availability above
    the floor, and the breaker walking open -> half-open -> closed.
    """
    from repro.serve import SLO_DEADLINE
    scen = "chaos"
    plan = build_chaos_plan(args)
    svc.register(scen, graph=graph, params=params, plan=plan)
    eng = svc.engine(scen)
    inj = eng.fault_injector
    assert inj is not None and eng.breaker is not None, \
        "chaos plan must arm the injector and the breaker"

    # warmup + fault-free references with the injector DISARMED: disarmed
    # pokes advance no counters, so compile-time traffic cannot consume
    # the deterministic fault counts. Scores depend only on uid % pool
    # (user feeds repeat across the universe), so one reference per pool
    # slot covers every uid in the drive.
    inj.set_armed(False)
    warm(svc, scen, wl, args.max_batch)
    pool = len(wl.ufeeds)
    refs = [svc.score(scen, wl.req(slot)).scores.copy()
            for slot in range(pool)]
    inj.set_armed(True)

    n_requests = 80
    futs = []
    for i in range(n_requests):
        uid = i % (pool * 6)      # revisit users: rebuild-after-quarantine
        dl = 1000.0 if i % 5 == 0 else None   # generous: never infeasible
        futs.append((uid, svc.submit(
            scen, wl.req(uid),
            slo=SLO_DEADLINE if dl is not None else "best_effort",
            deadline_ms=dl)))
        time.sleep(0.004)         # spread arrivals across breaker windows

    _wait_futures([f for _, f in futs], timeout=120.0)
    hung = [i for i, (_, f) in enumerate(futs) if not f.done()]
    assert not hung, f"hung futures (never resolved): {hung}"

    ok = 0
    failures: list[str] = []
    for uid, f in futs:
        if f.exception() is None:
            res = f.result()
            assert np.array_equal(res.scores, refs[uid % pool]), (
                f"chaos: successful response for uid={uid} is NOT "
                f"bit-identical to the fault-free reference")
            ok += 1
        else:
            failures.append(type(f.exception()).__name__)
    availability = ok / n_requests
    assert availability >= args.chaos_floor, (
        f"availability {availability:.3f} below floor {args.chaos_floor} "
        f"(failures: {failures})")

    # recovery: every fault count is exhausted by now (or exhausts on the
    # next few probes), so after each cooldown the half-open probe scores
    # a clean on-slots pack and the breaker closes — bounded rounds, no
    # sleep-and-hope
    for _ in range(10):
        if eng.breaker.state == "closed":
            break
        time.sleep(plan.ft.breaker_cooldown_ms / 1e3 + 0.02)
        svc.score(scen, wl.req(1))
    st = svc.stats()["scenarios"][scen]
    br = st["breaker"]
    assert br["opens"] >= 1, f"breaker never opened: {br}"
    assert br["closes"] >= 1 and br["state"] == "closed", (
        f"breaker never restored the fast path: {br}")
    assert st["device_store"]["quarantines"] >= 1, \
        "device tier was never quarantined"
    assert st["worker_crashes"] >= 1 and st["worker_respawns"] >= 1, (
        f"worker supervision never exercised: crashes="
        f"{st['worker_crashes']} respawns={st['worker_respawns']}")
    assert st["fallback_packs"] >= 1, \
        "breaker-open traffic never routed through the re-stack fallback"
    assert st["retries_attempted"] >= 1, "no retry was ever attempted"
    # fast path actually restored: a post-close request scores on slots
    post = svc.score(scen, wl.req(2))
    assert np.array_equal(post.scores, refs[2 % pool])
    assert eng.breaker.state == "closed"

    out = {
        "requests": n_requests, "ok": ok,
        "availability": round(availability, 4),
        "failure_types": sorted(set(failures)),
        "faults": st["faults"], "breaker": br,
        "quarantines": st["device_store"]["quarantines"],
        "worker_crashes": st["worker_crashes"],
        "worker_respawns": st["worker_respawns"],
        "fallback_packs": st["fallback_packs"],
        "corruptions_detected": st["corruptions_detected"],
        "retries_attempted": st["retries_attempted"],
        "retries_exhausted": st["retries_exhausted"],
        "plan": plan.to_dict(),
    }
    print(f"load/chaos,availability={availability:.3f},"
          f"ok={ok}/{n_requests},"
          f"faults={st['faults']['total_fired']},"
          f"quarantines={out['quarantines']},"
          f"breaker_opens={br['opens']},breaker_closes={br['closes']},"
          f"respawns={out['worker_respawns']},"
          f"retries={out['retries_attempted']}", flush=True)
    print("# chaos asserts passed", flush=True)
    return out


# ---------------------------------------------------------------------------
# Assertions
# ---------------------------------------------------------------------------

def smoke_asserts(results: dict) -> None:
    """Harness contracts — cheap, load-level, CI-gateable:

    * at the lowest (clearly sub-saturation) offered load, achieved qps
      tracks offered within 2x slack (the open loop is actually open);
    * the achieved curve is monotone-ish: more offered load never LOSES
      more than 30% of achieved throughput (no livelock cliff);
    * the deadline class is never shed at sub-saturation load (depth-based
      shedding must not touch it — only infeasible budgets can, and the
      smoke deadline budget is generous).
    """
    for preset, res in results.items():
        for variant, v in res["variants"].items():
            curve = v["curve"]
            lo = curve[0]
            assert lo["achieved_qps"] >= 0.5 * lo["offered_qps"], (
                f"{preset}/{variant}: achieved {lo['achieved_qps']} qps "
                f"<< offered {lo['offered_qps']} at the lowest load point")
            for a, b in zip(curve, curve[1:]):
                assert b["achieved_qps"] >= 0.7 * a["achieved_qps"], (
                    f"{preset}/{variant}: achieved qps fell "
                    f"{a['achieved_qps']} -> {b['achieved_qps']} as offered "
                    f"rose — throughput cliff under load")
            for pt in curve:
                if pt["fraction_of_saturation"] <= 0.9:
                    assert pt["shed_deadline"] == 0, (
                        f"{preset}/{variant}: {pt['shed_deadline']} deadline "
                        f"requests shed at sub-saturation load "
                        f"{pt['fraction_of_saturation']}x")
    print("# smoke asserts passed", flush=True)


def check_asserts(results: dict, tol: float) -> None:
    """The PR's acceptance: at fixed sub-saturation load the continuous
    loop's p99 must not exceed lockstep's, and its saturation qps must not
    be lower (within ``tol`` measurement slack for this shared-CPU box —
    the committed BENCH_load.json is expected to satisfy both strictly)."""
    for preset, res in results.items():
        c = res["comparison"]
        assert c["p99_ratio"] is not None and c["p99_ratio"] <= tol, (
            f"{preset}: continuous p99 {c['continuous_p99_ms']}ms > "
            f"{tol:g}x lockstep p99 {c['lockstep_p99_ms']}ms at "
            f"{c['sub_saturation_fraction']}x saturation")
        assert c["saturation_ratio"] >= 1.0 / tol, (
            f"{preset}: continuous saturation "
            f"{c['continuous_saturation_qps']} qps < lockstep "
            f"{c['lockstep_saturation_qps']} qps / {tol:g}")
    print("# check asserts passed", flush=True)


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small universe + short points + harness asserts "
                         "(the CI mode)")
    ap.add_argument("--check", action="store_true",
                    help="assert the continuous-vs-lockstep acceptance "
                         "criteria on this run")
    ap.add_argument("--chaos", action="store_true",
                    help="run the deterministic fault schedule instead of "
                         "the load curves and assert self-healing (zero "
                         "hung futures, bit-identical successes, breaker "
                         "recovery)")
    ap.add_argument("--chaos-floor", type=float, default=0.9,
                    help="minimum fraction of chaos requests that must "
                         "succeed")
    ap.add_argument("--json", metavar="PATH", default=None)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable ObsPlan tracing on every variant and "
                         "write a Perfetto-loadable Chrome trace here "
                         "(one track group per preset:variant scenario)")
    ap.add_argument("--presets", default=None,
                    help="comma list of ServePlan presets (default: "
                         "paper,vanilla; smoke: paper)")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--universe", type=int, default=None,
                    help="Zipf user universe (default 1_000_000; smoke "
                         "20_000)")
    ap.add_argument("--zipf-s", type=float, default=1.3)
    ap.add_argument("--pool", type=int, default=8,
                    help="distinct user-feed tensors reused across the "
                         "universe")
    ap.add_argument("--B", type=int, default=64,
                    help="candidate pool rows per request")
    ap.add_argument("--max-batch", type=int, default=1024)
    ap.add_argument("--linger-ms", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per measurement segment (default 1.0; "
                         "smoke 0.4)")
    ap.add_argument("--reps", type=int, default=None,
                    help="interleaved segments per (point, variant) "
                         "(default 3; smoke 2)")
    ap.add_argument("--workers", type=int, default=8,
                    help="closed-loop saturation probes")
    ap.add_argument("--fractions", default=None,
                    help="comma list of offered-load fractions of "
                         "saturation (default 0.3,0.6,0.9,1.2; smoke "
                         "0.4,1.5)")
    ap.add_argument("--deadline-frac", type=float, default=0.2)
    ap.add_argument("--deadline-ms", type=float, default=250.0)
    ap.add_argument("--shed-depth", type=int, default=64)
    ap.add_argument("--degrade-depth", type=int, default=32)
    ap.add_argument("--device-slots", type=int, default=256)
    ap.add_argument("--tol", type=float, default=1.10,
                    help="--check measurement slack")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.universe is None:
        args.universe = 20_000 if args.smoke else 1_000_000
    if args.duration is None:
        args.duration = 0.4 if args.smoke else 1.0
    if args.reps is None:
        args.reps = 2 if args.smoke else 3
    if args.presets is None:
        args.presets = "paper" if args.smoke else "paper,vanilla"
    if args.fractions is None:
        args.fractions = "0.4,1.5" if args.smoke else "0.3,0.6,0.9,1.2"
    presets = [p.strip() for p in args.presets.split(",") if p.strip()]
    args.fractions = [float(f) for f in args.fractions.split(",")]

    import jax
    from repro.graph.executor import init_graph_params
    from repro.models.ranking import (PaperRankingConfig,
                                      build_paper_ranking_model)
    from repro.serve import RankingService

    cfg = PaperRankingConfig().scaled(args.scale)
    graph, cfg = build_paper_ranking_model(cfg)
    params = init_graph_params(graph, jax.random.PRNGKey(args.seed))
    wl = Workload(graph, B=args.B, pool=args.pool, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    cdf = zipf_cdf(args.universe, args.zipf_s)
    ring = sample_users(cdf, 200_000, rng)
    hot = float(np.mean(ring < args.device_slots))
    print(f"# universe={args.universe} zipf_s={args.zipf_s} "
          f"(top-{args.device_slots} users carry {hot:.0%} of traffic)",
          flush=True)

    results = {}
    plans = {}
    with RankingService() as svc:
        if args.chaos:
            results["chaos"] = run_chaos(svc, graph, params, wl, args)
        else:
            for preset in presets:
                for variant in ("continuous", "lockstep"):
                    plan = build_plan(preset, variant, args)
                    svc.register(f"{preset}:{variant}", graph=graph,
                                 params=params, plan=plan)
                    warm(svc, f"{preset}:{variant}", wl, args.max_batch)
                    if variant == "continuous":
                        plans[preset] = plan.to_dict()
            for preset in presets:
                results[preset] = run_preset(svc, preset, wl, ring, args,
                                             rng)
                results[preset]["preset"] = preset
                results[preset]["plan"] = plans[preset]
        if args.trace:
            from repro.obs import write_trace
            tracers = {sc: svc.engine(sc).tracer for sc in svc.scenarios
                       if svc.engine(sc).tracer is not None}
            write_trace(args.trace, tracers)
            print(f"# wrote trace {args.trace} "
                  f"({sum(len(t) for t in tracers.values())} events)",
                  flush=True)

    if args.smoke and not args.chaos:
        smoke_asserts(results)
    if args.check and not args.chaos:
        check_asserts(results, args.tol)

    if args.json:
        payload = {
            "bench": "load", "config": "paper_ranking",
            "scale": args.scale, "universe": args.universe,
            "zipf_s": args.zipf_s, "pool_users": args.pool, "B": args.B,
            "hot_traffic_share": round(hot, 4),
            "duration_s": args.duration, "workers": args.workers,
            "fractions": args.fractions,
            "deadline_frac": args.deadline_frac,
            "deadline_ms": args.deadline_ms,
            "smoke": args.smoke, "presets": results,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
