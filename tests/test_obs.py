"""The repro.obs subsystem: ring-buffer tracer (concurrency contracts),
log-bucketed histograms, Chrome trace export, ObsPlan on the plan spine,
and the tracing/metrics wiring through engine + batcher + service.
"""
import glob
import json
import os
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.ranking import PaperRankingConfig, build_paper_ranking_model
from repro.obs import (Histogram, MetricsRegistry, Tracer, chrome_events,
                       merge_trace_files, span, trace_payload, write_trace)
from repro.serve import (CoalescingBatcher, ObsPlan, PlanError,
                         PlanResolutionWarning, RankingService, ServePlan,
                         ServeRequest, ServingEngine, StageProfiler)

from benchmarks.check_trace import validate


@pytest.fixture(scope="module")
def paper():
    graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.03))
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


TRACE_PLAN = ServePlan().evolve(obs__trace=True, batch__hedging=False)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_ring_wrap_keeps_newest(self):
        t = Tracer(capacity=8)
        for i in range(24):
            t.instant("e", i=i)
        assert len(t) == 8
        assert t.dropped == 16 and t.recorded == 24
        kept = [e[6]["i"] for e in t.events()]
        assert kept == list(range(16, 24))      # newest win

    def test_span_kinds_and_thread_stamp(self):
        t = Tracer()
        with span("work", tracer=t, group=1):
            pass
        t.begin("group", track="group:0", group=1)
        t.end("group", track="group:0", group=1)
        t.instant("hit", user=3)
        phases = [e[0] for e in t.events()]
        assert phases == ["X", "B", "E", "i"]
        tid = threading.get_ident()
        assert all(e[4] == tid for e in t.events())
        assert t.thread_names()[tid] == threading.current_thread().name

    def test_sampling(self):
        t = Tracer(sample_every=4)
        assert [s for s in range(9) if t.sampled(s)] == [0, 4, 8]
        assert all(Tracer().sampled(s) for s in range(5))

    def test_rejects_bad_params(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)
        with pytest.raises(ValueError):
            Tracer(sample_every=0)

    def test_concurrent_writers_no_negative_or_orphaned_spans(self):
        """Direct threads hammering one tracer: every complete span keeps a
        non-negative duration, B/E pairs stay balanced per synthetic
        track, and nothing is lost below capacity."""
        t = Tracer(capacity=100_000)
        n_threads, per = 8, 300

        def work(wid):
            for i in range(per):
                with span("op", tracer=t, wid=wid, i=i):
                    pass
                track = f"group:{wid}"
                t.begin("group", track=track, group=wid * per + i)
                t.instant("hit", wid=wid)
                t.end("group", track=track, group=wid * per + i)

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        evs = t.events()
        assert len(evs) == n_threads * per * 4 and t.dropped == 0
        assert all(e[3] >= 0.0 for e in evs if e[0] == "X")
        # balanced + never-negative depth per track, in buffer order
        depth = {}
        for ph, _, _, _, _, track, _ in evs:
            if ph == "B":
                depth[track] = depth.get(track, 0) + 1
            elif ph == "E":
                depth[track] = depth.get(track, 0) - 1
                assert depth[track] >= 0, "E before its B on one track"
        assert all(d == 0 for d in depth.values())
        # OS thread ids can be recycled across short-lived threads, so the
        # exact name count is not deterministic — but every recorded tid
        # must have been named
        assert {e[4] for e in evs} <= set(t.thread_names())


# ---------------------------------------------------------------------------
# Histogram / MetricsRegistry
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_percentiles_close_to_exact(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=2.0, sigma=1.0, size=20_000)
        h = Histogram("lat")
        for v in vals:
            h.record(float(v))
        for q in (50, 90, 99):
            exact = float(np.percentile(vals, q))
            est = h.percentile(q)
            # quarter-octave buckets: ±9% worst-case resolution
            assert abs(est - exact) / exact < 0.09, (q, est, exact)
        snap = h.snapshot()
        assert snap["count"] == len(vals)
        assert snap["min"] == pytest.approx(vals.min())
        assert snap["max"] == pytest.approx(vals.max())
        assert snap["mean"] == pytest.approx(vals.mean())

    def test_empty_and_single_value(self):
        h = Histogram()
        assert h.snapshot()["p99"] == 0.0 and h.snapshot()["count"] == 0
        h.record(7.25)
        # single observation: every percentile IS that value (clamping)
        for q in (50, 90, 99):
            assert h.percentile(q) == pytest.approx(7.25)

    def test_nonpositive_underflow_bucket(self):
        h = Histogram()
        for v in (0.0, -3.0, 5.0):
            h.record(v)
        snap = h.snapshot()
        assert snap["count"] == 3 and snap["min"] == -3.0
        assert h.percentile(99) == pytest.approx(5.0, rel=0.09)

    def test_reset_windows_the_distribution(self):
        h = Histogram()
        h.record(1000.0)                 # "warmup compile" outlier
        h.reset()
        for _ in range(50):
            h.record(2.0)
        assert h.snapshot()["max"] == 2.0 and h.snapshot()["count"] == 50

    def test_concurrent_record(self):
        h = Histogram()
        n_threads, per = 8, 2000

        def work(wid):
            for i in range(per):
                h.record(float(wid + 1))

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        snap = h.snapshot()
        assert snap["count"] == n_threads * per
        assert snap["total"] == pytest.approx(
            sum((w + 1) * per for w in range(n_threads)))

    def test_registry_gauges_and_histograms(self):
        reg = MetricsRegistry()
        assert reg.histogram("lat") is reg.histogram("lat")
        reg.histogram("lat").record(5.0)
        state = {"hits": 3}
        reg.gauge("hits", lambda: state["hits"])
        reg.gauge("dead", lambda: 1 / 0)
        snap = reg.snapshot()
        assert snap["hits"] == 3
        assert snap["lat"]["count"] == 1
        assert snap["dead"] is None      # dead gauge must not raise


# ---------------------------------------------------------------------------
# StageProfiler atomic snapshot (the satellite race fix)
# ---------------------------------------------------------------------------

class TestProfilerAtomicSnapshot:
    def test_snapshot_reset_loses_no_events(self):
        """Adder threads race a snapshot(reset=True) poller: the sum of all
        windowed snapshots plus the final remainder must equal exactly the
        number of adds — the old snapshot();reset() pair dropped whatever
        landed between the two calls."""
        prof = StageProfiler()
        n_threads, per = 6, 4000
        seen = [0]
        stop = threading.Event()

        def adder():
            for _ in range(per):
                prof.add("pack", 1e-9)

        def poller():
            while not stop.is_set():
                seen[0] += prof.snapshot(reset=True)["pack"]["calls"]

        threads = [threading.Thread(target=adder) for _ in range(n_threads)]
        pt = threading.Thread(target=poller)
        pt.start()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        stop.set()
        pt.join()
        seen[0] += prof.snapshot(reset=True)["pack"]["calls"]
        assert seen[0] == n_threads * per

    def test_snapshot_without_reset_preserves(self):
        prof = StageProfiler()
        prof.add("pack", 0.001)
        assert prof.snapshot()["pack"]["calls"] == 1
        assert prof.snapshot()["pack"]["calls"] == 1
        assert prof.snapshot(reset=True)["pack"]["calls"] == 1
        assert prof.snapshot()["pack"]["calls"] == 0


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------

class TestExport:
    def _tracer(self):
        t = Tracer()
        with span("pack", tracer=t, group=1):
            pass
        t.begin("group", track="group:0", group=1)
        t.instant("cache_hit", user="u1")
        t.end("group", track="group:0", group=1)
        return t

    def test_chrome_events_shape(self):
        evs, base = self._tracer(), None
        events, base = chrome_events(evs, pid=3, process_name="din")
        meta = [e for e in events if e["ph"] == "M"]
        assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
        # synthetic group track far above compacted real tids
        gtrack = [e for e in meta if e["args"]["name"] == "group:0"]
        assert gtrack and gtrack[0]["tid"] >= 1000
        real = [e for e in events if e["ph"] != "M"]
        assert all(e["pid"] == 3 for e in events)
        assert min(e["ts"] for e in real) == 0.0     # rebased to earliest
        x = [e for e in real if e["ph"] == "X"]
        assert x and all(e["dur"] >= 0.0 for e in x)

    def test_payload_validates_and_is_json(self, tmp_path):
        payload = write_trace(str(tmp_path / "t.json"),
                              {"a": self._tracer(), "b": self._tracer()})
        assert validate(payload) == []
        reloaded = json.loads((tmp_path / "t.json").read_text())
        assert validate(reloaded) == []
        assert {e["pid"] for e in reloaded["traceEvents"]} == {0, 1}

    def test_merge_assigns_shard_pids(self, tmp_path):
        paths = []
        for i in range(3):
            p = str(tmp_path / f"w{i}.json")
            write_trace(p, self._tracer())
            paths.append(p)
        merged = merge_trace_files(paths, str(tmp_path / "merged.json"))
        assert validate(merged) == []
        assert {e["pid"] for e in merged["traceEvents"]} == {0, 1, 2}
        names = {e["args"]["name"] for e in merged["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"shard-0", "shard-1", "shard-2"}

    def test_validator_catches_violations(self):
        ok = trace_payload(self._tracer())
        assert validate(ok) == []
        bad = json.loads(json.dumps(ok))
        bad["traceEvents"].append({"name": "group", "ph": "E",
                                   "pid": 9, "tid": 9, "ts": 1.0})
        assert any("E without open B" in m for m in validate(bad))
        neg = json.loads(json.dumps(ok))
        for e in neg["traceEvents"]:
            if e["ph"] == "X":
                e["dur"] = -1.0
        assert any("bad dur" in m for m in validate(neg))
        assert any("absent" in m
                   for m in validate(ok, require=["no_such_event"]))


# ---------------------------------------------------------------------------
# ObsPlan on the plan spine
# ---------------------------------------------------------------------------

class TestObsPlan:
    def test_defaults(self):
        plan = ServePlan()
        assert plan.obs == ObsPlan()
        assert plan.obs.trace is False and plan.obs.metrics is True

    def test_round_trip(self):
        plan = ServePlan().evolve(obs__trace=True, obs__trace_capacity=4096,
                                  obs__sample_every=8, obs__metrics=False)
        again = ServePlan.from_json(plan.to_json())
        assert again == plan and again.obs.trace_capacity == 4096

    def test_rejects(self):
        with pytest.raises(PlanError):
            ServePlan(obs=ObsPlan(trace=True, trace_capacity=0))
        with pytest.raises(PlanError):
            ServePlan(obs=ObsPlan(trace=True, sample_every=0))

    def test_resolves_trace_knobs_without_trace(self):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            plan = ServePlan(obs=ObsPlan(trace=False, trace_capacity=4096,
                                         sample_every=8))
        assert any(issubclass(x.category, PlanResolutionWarning) for x in w)
        assert plan.obs.trace_capacity is None
        assert plan.obs.sample_every == 1
        assert any("without trace=True" in n for n in plan.resolution_notes)


# ---------------------------------------------------------------------------
# Engine + batcher + service wiring
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_off_by_default(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan())
        assert eng.tracer is None and eng.metrics is not None
        eng.close()

    def test_linkage_survives_out_of_order_collect(self, paper):
        """Two in-flight groups collected in reverse order: each group's
        B/E pair lands on ITS OWN synthetic track with its own gid, so
        the overlap renders instead of corrupting."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=TRACE_PLAN)
        h1 = eng.begin_coalesced([_request(graph, user_in, 1, 9, seed=1)])
        h2 = eng.begin_coalesced([_request(graph, user_in, 2, 9, seed=2)])
        eng.collect(h2)                          # out of order
        eng.collect(h1)
        assert h1.gid != h2.gid
        assert h1.track != h2.track
        groups = [e for e in eng.tracer.events() if e[1] == "group"]
        by_track = {}
        for ph, _, _, _, _, track, args in groups:
            by_track.setdefault(track, []).append((ph, args["group"]))
        for track, seq in by_track.items():
            phs = [p for p, _ in seq]
            gids = {g for _, g in seq}
            assert phs == ["B", "E"], (track, phs)
            assert len(gids) == 1                # B and E carry the same gid
        # slots freed: a third group reuses the lowest slot
        h3 = eng.begin_coalesced([_request(graph, user_in, 3, 9, seed=3)])
        assert h3.track == "group:0"
        eng.collect(h3)
        assert validate(trace_payload(eng.tracer)) == []
        eng.close()

    def test_exception_in_begin_closes_group_span(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=TRACE_PLAN)
        req = _request(graph, user_in, 1, 9, seed=1)
        # uncached user with no user feeds: stage 1 fails mid-begin
        bad = ServeRequest(user_id=999, user_feeds={},
                           candidate_feeds=req.candidate_feeds)
        with pytest.raises(Exception):
            eng.begin_coalesced([bad])
        assert validate(trace_payload(eng.tracer)) == []   # B/E balanced
        h = eng.begin_coalesced([req])           # slot was released
        assert h.track == "group:0"
        eng.collect(h)
        eng.close()

    def test_batcher_stream_trace_and_stats(self, paper):
        """The full wiring under the batcher's worker thread + submitter
        threads: spans stay well-formed, request→group linkage holds, and
        the histogram surface reports percentiles."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=TRACE_PLAN.evolve(
            batch__continuous=True, batch__max_inflight=2))
        reqs = [_request(graph, user_in, i % 3, 7 + (i % 3) * 8, seed=i)
                for i in range(18)]
        with CoalescingBatcher.from_plan(eng, eng.plan.batch) as b:
            futs = []
            def submit(chunk):
                futs_local = [b.submit(r) for r in chunk]
                futs.extend(futs_local)
            threads = [threading.Thread(target=submit,
                                        args=(reqs[i::3],))
                       for i in range(3)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            res = [f.result() for f in futs]
        assert len(res) == len(reqs)

        evs = eng.tracer.events()
        names = {e[1] for e in evs}
        assert {"submit", "queue_claim", "group_launch", "resolve",
                "group", "pack", "dispatch", "collect"} <= names
        assert {"cache_hit", "cache_miss"} & names
        # every request's own resolve instant is recorded, apart from the
        # group's resolve_group span; idle waits stay out of the ring
        resolves = [e for e in evs if e[1] == "resolve"]
        assert len(resolves) == len(reqs)
        assert {e[0] for e in resolves} == {"i"}
        assert {e[0] for e in evs if e[1] == "resolve_group"} == {"X"}
        assert "wait" not in names
        # linkage: every group_launch's req seqs were also submitted, and
        # its gid matches a traced group span
        submitted = {e[6]["req"] for e in evs if e[1] == "submit"}
        gids = {e[6]["group"] for e in evs if e[1] == "group"}
        launches = [e[6] for e in evs if e[1] == "group_launch"]
        assert launches
        for args in launches:
            assert set(args["reqs"]) <= submitted
            if args.get("group") is not None:
                assert args["group"] in gids
        assert validate(trace_payload(eng.tracer)) == []

        # histogram surface: percentiles + compat total
        lat = b.request_latency.snapshot()
        assert lat["count"] == len(reqs) and lat["p99"] >= lat["p50"] > 0
        qw = b.queue_wait.snapshot()
        assert qw["count"] == len(reqs)
        assert b.queue_wait_ms == pytest.approx(qw["total"])
        snap = b.metrics.snapshot()
        assert snap["requests"] == len(reqs)
        assert snap["cache_hits"] == eng.cache.hits
        eng.close()

    def test_service_stats_percentiles(self, paper):
        graph, params, user_in = paper
        svc = RankingService(TRACE_PLAN)
        svc.register("ranking", graph=graph, params=params)
        for i in range(6):
            svc.score("ranking", _request(graph, user_in, i % 2, 9, seed=i))
        st = svc.stats()["scenarios"]["ranking"]
        lat = st["latency"]
        assert lat["request_ms"]["count"] == 6
        assert lat["request_ms"]["p99"] >= lat["request_ms"]["p50"] > 0
        assert lat["queue_wait_ms"]["count"] == 6
        assert st["metrics"]["cache_hits"] == st["cache_hits"] \
            if "cache_hits" in st else True
        assert st["metrics"]["pipeline_forks"] == st["pipeline_forks"]
        assert st["queue_wait_ms"] == pytest.approx(
            lat["queue_wait_ms"]["total"])
        svc.close()

    def test_metrics_off_keeps_compat_surface(self, paper):
        """obs.metrics=False: the engine registry is gone, but the batcher
        falls back to a private registry so queue_wait_ms and the latency
        snapshots keep working."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan().evolve(
            obs__metrics=False, batch__hedging=False))
        assert eng.metrics is None
        with CoalescingBatcher(eng, linger_ms=1.0) as b:
            b.submit(_request(graph, user_in, 0, 9, seed=0)).result()
            b.submit(_request(graph, user_in, 0, 9, seed=1)).result()
            assert b.queue_wait_ms >= 0.0
            assert b.request_latency.snapshot()["count"] == 2
        svc_stats_like = b.metrics.snapshot()
        assert svc_stats_like["requests"] == 2
        eng.close()

    def test_tracing_engine_scores_bit_identical(self, paper):
        graph, params, user_in = paper
        reqs = [_request(graph, user_in, i, 9 + i, seed=i) for i in range(3)]
        plain = ServingEngine(graph, params, plan=ServePlan().evolve(
            batch__hedging=False))
        traced = ServingEngine(graph, params, plan=TRACE_PLAN)
        for r in reqs:
            a = plain.score(r)
            bres = traced.score(r)
            assert np.array_equal(a.scores, bres.scores)
        assert len(traced.tracer) > 0
        plain.close()
        traced.close()

    def test_sample_every_thins_request_events(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=TRACE_PLAN.evolve(
            obs__sample_every=1000))
        with CoalescingBatcher(eng, linger_ms=1.0) as b:
            for i in range(5):
                b.submit(_request(graph, user_in, 0, 9, seed=i)).result()
        names = [e[1] for e in eng.tracer.events()]
        # group-level spans are never thinned; per-request instants are
        assert "group" in names and "pack" in names
        assert names.count("submit") <= 1
        eng.close()

    def test_cache_evict_and_store_instants(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=TRACE_PLAN.evolve(
            cache__max_cached_users=2))
        for uid in range(4):
            eng.score(_request(graph, user_in, uid, 9, seed=uid))
        names = [e[1] for e in eng.tracer.events()]
        assert "cache_evict" in names
        eng.close()


# ---------------------------------------------------------------------------
# Spans on the profiler's clock (repro.obs.trace.span)
# ---------------------------------------------------------------------------

# every span the serving hot path opens, by the thread it runs on
WORKER_SPANS = {"wait", "linger", "begin_coalesced", "stage1", "stage1_wait",
                "slots", "pack", "fill", "h2d", "dispatch", "device",
                "collect", "unpack", "resolve_group"}
HEDGE_SPANS = {"stage2"}
# StageProfiler phase -> the spans whose durations it totals
PHASE_SPANS = {"stage1": ("stage1",), "pack": ("slots", "pack"),
               "dispatch": ("dispatch",), "device": ("device",),
               "unpack": ("unpack",), "queue_idle": ("wait",)}


def _profiled_spans(logdir):
    """(line index, start ns, end ns, name, args) of every ``serve.*``
    event on the host plane of the trace written under ``logdir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for li, line in enumerate(plane.lines):
                out.extend((li, e.start_ns, e.end_ns, e.name[len("serve."):],
                            dict(e.stats))
                           for e in line.events
                           if e.name.startswith("serve."))
    return out


class TestProfilerSpans:
    def test_serving_spans_reach_the_profiler_clock(self, paper, tmp_path):
        """An engine (hedging on) behind a continuous batcher, traced with
        the Python tracer off: every span lands on the host plane with a
        group id, children nest in their parents, and each StageProfiler
        phase totals its spans."""
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan().evolve(
            batch__hedging=True, batch__continuous=True))
        eng.score(_request(graph, user_in, 100, 9, seed=100))  # warm U=1
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.profiler.snapshot(reset=True)
            with CoalescingBatcher.from_plan(eng, eng.plan.batch) as b:
                # a new bucket first (a blocking compile launch: the
                # device span), then hedged launches one group at a time
                for i, n in enumerate((200, 9, 12, 9)):
                    b.submit(_request(graph, user_in, i, n, seed=i)
                             ).result(timeout=120)
                    time.sleep(0.06)            # a wait or two between
            snap = eng.profiler.snapshot()
        finally:
            jax.profiler.stop_trace()
            eng.close()
        spans = _profiled_spans(str(tmp_path))
        names = {s[3] for s in spans}
        assert WORKER_SPANS | HEDGE_SPANS <= names
        assert all(isinstance(s[4].get("group"), int) for s in spans)
        # the worker's spans sit on one line, the hedged stage 2 elsewhere
        worker = {s[0] for s in spans if s[3] in ("wait", "linger")}
        assert len(worker) == 1
        assert {s[0] for s in spans if s[3] == "stage2"} - worker

        def inside(child, parent):
            kids = [s for s in spans if s[3] == child]
            assert kids
            for li, s, e, _, args in kids:
                assert any(p[0] == li and p[1] <= s and e <= p[2]
                           and p[4]["group"] == args["group"]
                           for p in spans if p[3] == parent), (child, s)

        inside("fill", "pack")
        inside("h2d", "pack")
        inside("stage1_wait", "stage1")
        inside("pack", "begin_coalesced")
        inside("unpack", "collect")
        for phase, members in PHASE_SPANS.items():
            mine = [s for s in spans if s[3] in members]
            ms = sum(e - s for _, s, e, _, _ in mine) / 1e6
            assert snap[phase]["calls"] == len(mine) > 0, phase
            assert abs(snap[phase]["total_ms"] - ms) <= 0.05 * ms + 0.05, (
                phase, snap[phase]["total_ms"], ms)

    def test_h2d_bytes_count_the_padded_buffers(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan().evolve(
            batch__hedging=False))
        want = 0
        for uid, n in ((0, 9), (1, 300)):
            req = _request(graph, user_in, uid, n, seed=uid)
            row = 4 + sum(np.asarray(v).dtype.itemsize
                          * int(np.prod(np.shape(v)[1:]))
                          for v in req.candidate_feeds.values())
            eng.score(req)
            want += eng._bucket(n) * row
            assert eng.h2d_bytes == want
        assert eng._bucket(9) > 9                # padding rows counted
        eng.close()
        svc = RankingService(ServePlan().evolve(batch__hedging=False))
        svc.register("ranking", graph=graph, params=params)
        svc.score("ranking", _request(graph, user_in, 0, 9, seed=0))
        st = svc.stats()["scenarios"]["ranking"]
        assert st["h2d_bytes"] == svc.engine("ranking").h2d_bytes > 0
        svc.close()

    def test_executables_carry_stable_names(self, paper):
        graph, params, user_in = paper
        eng = ServingEngine(graph, params, plan=ServePlan().evolve(
            batch__hedging=False, cache__device_resident=True))
        eng.score(_request(graph, user_in, 0, 9, seed=0))
        exes = eng.stage2_executables()
        assert exes and all("jit_serve_stage2" in c.as_text()
                            for c in exes.values())
        feeds = {k: v for k, v in _request(graph, user_in, 1, 9, seed=1)
                 .user_feeds.items() if k in eng._stage1_inputs}
        assert "jit_serve_stage1" in eng._stage1.lower(
            eng.params, feeds).as_text()
        tables = eng.device_store.tables
        assert "jit_serve_rep_write" in eng.device_store._writer.lower(
            tables, {k: t[:1] for k, t in tables.items()}, 0).as_text()
        eng.close()

    def test_span_costs_microseconds_without_a_session(self):
        """With no profiler session the annotation is close to free; the
        span still feeds the profiler phase and the ring tracer."""
        prof, trc = StageProfiler(), Tracer()
        with span("pack", phase="pack", profiler=prof, tracer=trc,
                  group=7) as sp:
            sp.set(rows=3)
        assert prof.snapshot()["pack"]["calls"] == 1
        assert prof.snapshot()["pack"]["total_ms"] == pytest.approx(
            sp.seconds * 1e3)
        (ph, name, _, dur, _, _, args), = trc.events()
        assert (ph, name, dur, args) == ("X", "pack", sp.seconds,
                                         {"group": 7, "rows": 3})
        n = 20000
        t0 = time.perf_counter()
        for i in range(n):
            with span("pack", group=i):
                pass
        assert (time.perf_counter() - t0) / n < 50e-6
