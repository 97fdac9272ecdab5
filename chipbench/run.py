"""Run one benchmark cell once and print its result as the last line.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name (``BENCHMARK.json``, ``chipbench/spec.py``). A run:

1. set-up (``setup_s``, from process start to the first timed request):
   builds the program's model from the ``repro.configs`` registry, makes
   the benchmark's weights from the seed on the device, serves them
   through ``RankingService`` (batcher, engine, rep cache, stage-2
   kernels) under the configuration's plan, makes the traffic from the
   seed, compiles (or reads from JAX's persistent cache) every stage-2
   shape the traffic can form, and fills the rep cache with the mix's
   most popular users;
2. the window: ``--seconds`` of open-loop or closed-loop traffic through
   ``RankingService.submit``; every request due in the window is waited
   for, up to a minute past its close;
3. ``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: a profiler
   trace of a few seconds in the middle of the window and the cell's
   per-layer metrics, read by ``chipbench/metrics/<metric>.py``;
4. after the program is closed and its arrays freed: the served scores of
   a seeded sample of the window's requests against the plain float32
   reference (``check.py``), which decides ``correct``.

It needs a TPU: with none, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                                            # noqa: E402
import gc                                                  # noqa: E402
import json                                                # noqa: E402
import pathlib                                             # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, spec                          # noqa: E402

TRACE_SECONDS = 4.0          # longest traced stretch inside the window
DRAIN_SECONDS = 60.0         # wait past the window's close for its requests
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "scratch directory in the checkout, removed)")
    return ap.parse_args(argv)


class CompileCounter:
    """Counts XLA compiles (persistent-cache reads included) inside a
    ``with`` block."""

    def __enter__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def stage2_groups(batch, lo: int, hi: int) -> list[list[int]]:
    """Pool sizes of request groups that together form every stage-2 shape
    (users in the pack, padded to a power of two; row bucket) that traffic
    with pools in [lo, hi] can form under the plan's ``batch`` section."""
    def bucket(n):
        return min(batch.max_batch, max(batch.min_bucket, next_pow2(n)))

    kmax = max(1, min(batch.max_users_per_batch, batch.max_batch // lo))
    buckets = sorted({bucket(n) for n in (lo, min(hi * kmax,
                                                  batch.max_batch))}
                     | {b for b in (1 << e for e in range(31))
                        if bucket(lo) <= b <= batch.max_batch})
    groups, seen = [], set()
    for k in range(1, kmax + 1):
        for b in buckets:
            total = min(b, k * hi)
            if total < k * lo or bucket(total) != b:
                continue
            key = (next_pow2(k), b)
            if key in seen:
                continue
            seen.add(key)
            groups.append([total // k + (j < total % k) for j in range(k)])
    return groups


def run_cell(args, *, root: pathlib.Path = ROOT, require_tpu: bool = True,
             controls: dict | None = None,
             sweep: list[dict] | None = None,
             mix_over: dict | None = None) -> dict | None:
    """One run of one cell; returns the result line's dict, or None when
    the device is refused. With ``controls`` {name: mm}, the reference at
    each control's precision is also put in the program's place on the
    same sample and judged as the served answers are (``"controls"``: its
    widest gap and its ``correct``). With ``sweep``, a list of
    traffic-parameter overrides, set-up is followed by one window per
    override and only their readings are returned (``"sweep"``).
    ``mix_over`` overrides traffic parameters for the whole run."""
    import jax
    import numpy as np

    from chipbench import model, trace as tracemod
    from chipbench.traffic import (Recorder, Traffic, closed_loop, open_loop,
                                   readings, seed_rngs)

    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    cfg = spec.load_config(root, bench, cell["config"])
    mix = {**spec.load_traffic(root, bench, cell["traffic"]),
           **(mix_over or {})}
    ref_mod = spec.load_reference(root, bench, cell["config"])
    bdir = spec.bench_dir(root, bench)
    peaks = json.loads((bdir / "peaks.json").read_text())
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = spec.cell_metrics(bench, cell["name"], kind)
    readers = ({m["name"]: spec.load_reader(root, bench, m["name"])
                for m in metrics} if args.trace else {})

    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_tpu and dev.platform != "tpu":
        log(f"refused: needs a TPU, JAX found {dev.platform!r}")
        return None
    if len(devs) < cell["chips"]:
        log(f"refused: the cell asks for {cell['chips']} chips, "
            f"JAX found {len(devs)}")
        return None
    peak = peaks["devices"].get(dev.device_kind)
    if peak is None and require_tpu:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} in "
                         f"peaks.json: {sorted(peaks['devices'])}")

    from repro.common import enable_compile_cache
    from repro.serve import ServeRequest
    log(f"compile cache: {enable_compile_cache()}")

    # ---- set-up -----------------------------------------------------------
    t = time.perf_counter()
    shapes = ref_mod.param_shapes(cfg)
    graph = model.program_graph(cfg)
    model.check_param_shapes(graph, shapes)
    weights = model.make_weights(shapes, cfg["init"], args.seed)
    jax.block_until_ready(weights)
    svc = model.build_service(cfg, graph, weights)
    scenario = cfg["registry"]
    engine = svc.engine(scenario)
    t_model = time.perf_counter() - t

    t = time.perf_counter()
    traffic = Traffic(mix, ref_mod.input_specs(cfg), cfg, args.seed,
                      args.seconds)
    t_traffic = time.perf_counter() - t

    def spare(uid: int, n: int) -> ServeRequest:
        # set-up requests: user ids above every traffic id, features and
        # candidates from the traffic's own arrays
        return ServeRequest(user_id=uid, user_feeds=traffic.user_feeds(uid),
                            candidate_feeds={k: v[:n] for k, v in
                                             traffic.cand_arrays.items()})

    t = time.perf_counter()
    lo, hi = mix["pool"]["min"], mix["pool"]["max"]
    groups = stage2_groups(engine.plan.batch, lo, hi)
    next_uid = 10**12
    for rep in range(2):        # the second pass takes the steady dispatch
        for sizes in groups:
            engine.score_coalesced([spare(next_uid + j, n)
                                    for j, n in enumerate(sizes)])
            next_uid += len(sizes)
    burst = [svc.submit(scenario, spare(next_uid + j, int(traffic.sizes[j])))
             for j in range(16)]
    for f in burst:
        f.result()
    next_uid += 16
    t_shapes = time.perf_counter() - t

    t = time.perf_counter()
    warm = traffic.warm_users()
    fill = max((g for g in groups if all(n == lo for n in g)), key=len,
               default=[lo])
    per = len(fill)
    for j in range(0, len(warm), per):
        uids = warm[j:j + per]
        engine.score_coalesced([ServeRequest(
            user_id=u, user_feeds=traffic.user_feeds(u),
            candidate_feeds={k: v[:lo] for k, v in
                             traffic.cand_arrays.items()}) for u in uids])
    t_fill = time.perf_counter() - t
    log(f"set-up: model+weights {t_model:.3f}s traffic {t_traffic:.3f}s "
        f"shapes {t_shapes:.3f}s ({len(groups)} groups, "
        f"{engine.stage2_compilations} stage-2 executables) cache fill "
        f"{t_fill:.3f}s ({len(warm)} users)")

    # ---- window -----------------------------------------------------------
    def window_pass(traffic, mix, trace: bool) -> dict:
        """One measured window of ``traffic``; returns its readings."""
        def request(i: int) -> ServeRequest:
            uid = int(traffic.uids[i])
            return ServeRequest(user_id=uid,
                                user_feeds=traffic.user_feeds(uid),
                                candidate_feeds=traffic.cand_feeds(i))

        stats0 = svc.stats()
        if queue_wait is not None:
            queue_wait.reset()
        s2_exes0 = engine.stage2_compilations
        gc.collect()
        gc.disable()
        rec = Recorder(traffic.n)
        trace_info: dict = {}
        tracer_thread = None
        if trace:
            import threading
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_len = min(TRACE_SECONDS, args.seconds)
            lead = (args.seconds - trace_len) / 2

            def tracer():
                time.sleep(lead)
                jax.profiler.start_trace(str(trace_dir))
                trace_info["t0"] = time.perf_counter()
                time.sleep(trace_len)
                trace_info["t1"] = time.perf_counter()
                jax.profiler.stop_trace()

            tracer_thread = threading.Thread(target=tracer, daemon=True)

        def submit(i):
            return svc.submit(scenario, request(i))

        setup_s = time.perf_counter() - T_PROCESS
        if tracer_thread is not None:
            tracer_thread.start()
        with CompileCounter() as compiles:
            if mix["loop"] == "open":
                t0, sent = open_loop(submit, traffic, args.seconds, rec)
            else:
                t0, sent = closed_loop(submit, traffic, args.seconds,
                                       mix["clients"], rec)
            t_close = t0 + args.seconds
            drained = rec.wait_all(max(1.0, t_close + DRAIN_SECONDS
                                       - time.perf_counter()))
        if tracer_thread is not None:
            tracer_thread.join()
        window_compiles = compiles.count
        gc.enable()

        r = readings(rec, traffic, t0, sent, args.seconds)
        ok, done, in_window = r["ok"], r["done"], r["in_window"]
        sizes = traffic.sizes[:sent]
        cand_window = int(sizes[in_window].sum())
        values = {k: r[k] for k in ("p50_ms", "p95_ms", "p99_ms",
                                    "candidates_per_s") if k in r}
        values["setup_s"] = setup_s
        if mix["loop"] == "open":
            late = r.get("late_ms", np.zeros(1))
            log(f"open loop at {mix['rate_per_s']}/s: {sent} due in the "
                f"window, {int(ok.sum())} served; generator late p50 "
                f"{np.percentile(late, 50):.3f}ms p99 "
                f"{np.percentile(late, 99):.3f}ms max {late.max():.3f}ms")
        else:
            log(f"closed loop, {mix['clients']} clients: {sent} sent, "
                f"{int(in_window.sum())} finished in the window")
        quarters = np.histogram(done[in_window] - t0, bins=4,
                                range=(0.0, args.seconds),
                                weights=sizes[in_window])[0]
        log(f"candidates finished in each quarter of the window: "
            f"{quarters.astype(int).tolist()}")
        log(f"window readings: {json.dumps(values)}")

        stats1 = svc.stats()
        sc0 = stats0["scenarios"][scenario]
        sc1 = stats1["scenarios"][scenario]
        counters = {k: sc1[k] - sc0[k]
                    for k in ("stage1_calls", "stage2_calls")}
        for k in ("hits", "misses"):
            counters[f"cache_{k}"] = (stats1["shared_cache"][k]
                                      - stats0["shared_cache"][k])
        profile_ms = {p: sc1["profile"][p]["total_ms"]
                      - sc0["profile"][p]["total_ms"]
                      for p in sc0["profile"]}
        log(f"window: {window_compiles} compiles (stage-2 executables "
            f"added: {engine.stage2_compilations - s2_exes0}), counters "
            f"{json.dumps(counters)}, profile_ms {json.dumps(profile_ms)}, "
            f"drained={drained}, "
            f"unfinished={sent - len(rec.results) - len(rec.errors)}")
        return {
            "values": values, "rec": rec, "sent": sent, "ok": ok, "t0": t0,
            "done": done, "sizes": sizes, "in_window": in_window,
            "trace_info": trace_info,
            "window": {
                "seconds": args.seconds, "requests": int(in_window.sum()),
                "candidates": cand_window, "counters": counters,
                "profile_ms": profile_ms,
                "queue_wait": (queue_wait.snapshot()
                               if queue_wait is not None else None),
                "flops_per_candidate":
                    ref_mod.stage2_flops_per_candidate(cfg),
                "peak": peak, "trace": None}}

    queue_wait = (engine.metrics.histogram("queue_wait_ms")
                  if engine.metrics is not None else None)
    trace_dir = pathlib.Path(args.trace_dir or
                             root / ".chipbench_runs" / "trace")
    if sweep:
        out = []
        for k, over in enumerate(sweep):
            # a seed of its own for each point: under a fresh-user mix no
            # point finds an earlier point's users in the rep cache
            mix_w = {**mix, **over}
            traffic_w = Traffic(mix_w, ref_mod.input_specs(cfg), cfg,
                                args.seed + 1 + k, args.seconds)
            w = window_pass(traffic_w, mix_w, False)
            point = {**over, **w["values"], "sent": w["sent"],
                     "served": int(w["ok"].sum()),
                     "in_window": int(w["in_window"].sum()),
                     "counters": w["window"]["counters"]}
            if mix_w["loop"] == "open" and w["sent"] >= 30:
                # a growing backlog: the last third waits longer than the
                # first
                lat = w["done"] - w["t0"] - traffic_w.due[:w["sent"]]
                third = w["sent"] // 3
                point["p50_ms_first_third"] = float(
                    np.nanmedian(lat[:third])) * 1e3
                point["p50_ms_last_third"] = float(
                    np.nanmedian(lat[-third:])) * 1e3
            out.append(point)
        svc.close()
        return {"sweep": out}
    wp = window_pass(traffic, mix, bool(args.trace))
    values, rec, sent, ok, done = (wp["values"], wp["rec"], wp["sent"],
                                   wp["ok"], wp["done"])
    sizes, in_window, trace_info = (wp["sizes"], wp["in_window"],
                                    wp["trace_info"])
    window = wp["window"]
    failed = int(sent - ok.sum())
    memory_peak = int(max((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0) for d in devs[:cell["chips"]]))

    # ---- the program is closed and its arrays freed -----------------------
    results = {int(i): rec.results[int(i)] for i in np.flatnonzero(in_window)}
    svc.close()
    del svc, engine, queue_wait, weights, burst, wp
    gc.collect()

    out_device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell["chips"], "memory_peak_bytes": memory_peak}
    breakdown = None
    if args.trace:
        path = tracemod.find_xplane(str(trace_dir))
        if path is None:
            raise SystemExit(f"no trace written under {trace_dir}")
        red = tracemod.reduce(path, ref_mod.kernel_sites(cfg), peak)
        span = trace_info["t1"] - trace_info["t0"]
        traced = ok & (done >= trace_info["t0"]) & (done <= trace_info["t1"])
        red["window_s"] = span
        red["candidates"] = int(sizes[traced].sum())
        window["trace"] = red
        out_device["busy_s"] = red.get("busy_s", 0.0)
        out_device["window_s"] = span
        breakdown = {"device_ops": red.get("device_ops", []),
                     "idle_gaps": red.get("idle_gaps", [])}
        log(f"trace: {path} ({pathlib.Path(path).stat().st_size} bytes), "
            f"busy {red.get('busy_s')}s of {span}s, kernels "
            f"{json.dumps(red.get('kernels'))}")
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- correctness ------------------------------------------------------
    t = time.perf_counter()
    ref_weights = model.make_weights(shapes, cfg["init"], args.seed)
    sample = check.pick_sample(results, traffic.sizes,
                               seed_rngs(args.seed, 4)[3])
    reference = model.Reference(ref_mod, cfg, ref_weights)
    reading = check.compare(sample, results, traffic, reference)
    limit = cfg["check"]["max_abs_err"]
    control_readings = {}
    for name, mm in (controls or {}).items():
        # the reference at a lower precision in the program's place, through
        # the same comparison and verdict as the served answers
        answers = check.stand_in(sample, results, traffic, model.Reference(
            ref_mod, cfg, ref_weights, mm=mm))
        r = check.compare(sample, answers, traffic, reference)
        control_readings[name] = {"max_abs_err": r["max_abs_err"],
                                  "correct": check.verdict(r, 0, limit)}
        log(f"control {name}: {json.dumps(control_readings[name])}")
    log(f"reference over {json.dumps(reading)} in "
        f"{time.perf_counter() - t:.3f}s")
    correct = check.verdict(reading, failed, limit)

    if args.trace:
        out_metrics = {}
        for m in metrics:
            v = readers[m["name"]](window)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        out_metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]} for m in metrics}
    checks = {"max_abs_err": {"value": reading["max_abs_err"],
                              "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    result = {"correct": correct, "attempted": int(sent), "failed": failed,
              "metrics": out_metrics, "device": out_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reading"] = reading
    if controls:
        result["controls"] = control_readings
    result["checks"] = checks          # last: the numbers beside their limits
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run_cell(args)
    if result is None:
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
