"""Multi-process SPMD serving runner.

Every worker process runs the IDENTICAL program: build the paper's ranking
graph from a fixed seed, construct a ``ServingEngine`` with
``shard_candidates=True`` (the 'cand' mesh spans all processes' devices
after ``jax.distributed`` initializes), and drive the same request
sequence in lockstep. Stage 2's inputs are globalized onto the mesh —
candidate rows and the per-row user index sharded, params and rep tables
replicated — so each worker's devices score their candidate slice and the
closing all-gather (the step's one collective) hands every host the full
score vector.

Correctness contract (the subprocess test in ``tests/test_dist.py``):
with ``--verify`` every worker checks its sharded scores against the plain
float32 reference (``repro.serve.reference``) within the platform's stated
tolerance — sharding changes the executable's shapes, and XLA promises no
bit-equality across shapes.

Usage (spawner re-execs itself as the workers)::

  JAX_PLATFORMS=cpu python -m repro.dist.runner --spawn 2 \
      --devices-per-process 2 --verify
  python -m repro.dist.runner --spawn 1 --bench    # a TPU host: all chips
  JAX_PLATFORMS=cpu python -m repro.dist.runner --spawn 2 --plan plan.json

Devices: the runner never chooses the platform. When the caller has chosen
the CPU (``JAX_PLATFORMS=cpu``, as tests and rehearsals do), each worker
gets ``--devices-per-process`` forced host devices. On an accelerator host
one process drives every local chip, so ``--spawn`` above 1 is refused
there (several processes cannot share one host's chips).

Each worker prints one JSON record per mode; the spawner re-emits worker
0's stdout and fails if any worker fails.

The serving configuration travels as a serialized ``ServePlan``: the
spawner resolves ONE plan (``--plan`` file or the flag defaults, sharding
forced on) and ships it to every worker as ``--plan-json``, so workers
build their engines from the identical declarative config instead of
re-parsing argv flags — the plan JSON is the single source of truth for
the SPMD fleet's engine shape.
"""
from __future__ import annotations

import os

# The forced host-device count must be locked in before any jax import
# (the spawner sets REPRO_HOST_DEVICES in each worker's environment). It
# only stands in for chips when the caller already chose the CPU.
if (os.environ.get("REPRO_HOST_DEVICES")
        and os.environ.get("JAX_PLATFORMS") == "cpu"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_HOST_DEVICES"])

import argparse
import json
import socket
import subprocess
import sys
import time

from repro.serve.plan import ServePlan

MODES = ("vani", "uoi", "mari")


def build_plan(args) -> ServePlan:
    """The fleet's serving plan: an optional ``--plan`` JSON file with the
    runner's operating requirement layered on top — candidate-axis
    sharding on (that is what this runner exists to drive).

    Flag overrides beat the plan file only when EXPLICITLY given; without
    a plan file the runner's own bench-sized defaults apply. A plan file's
    ``max_batch``/``min_bucket``/``compress_scores`` therefore survive
    unless the caller asks otherwise."""
    base = ServePlan.load(args.plan) if args.plan else ServePlan()
    over = {}
    if not base.shard.shard_candidates:
        # force sharding ON, but keep a plan file's explicit shard COUNT
        over["shard__shard_candidates"] = True
    if args.max_batch is not None:
        over["batch__max_batch"] = args.max_batch
    elif not args.plan:
        over["batch__max_batch"] = 256
    if args.min_bucket is not None:
        over["batch__min_bucket"] = args.min_bucket
    elif not args.plan:
        over["batch__min_bucket"] = 16
    if args.compress_scores:             # store_true: only ever forces ON
        over["shard__compress_scores"] = True
    if getattr(args, "device_resident", False):
        # persistent device rep tables (serve/cache.DeviceRepStore). On a
        # single-process mesh the sharded engine stores the tables with
        # the replicated boundary shardings and skips per-pack re-stacking;
        # multi-process engines fall back at engine level (per-process
        # asynchronous table writes cannot stay SPMD-identical).
        over["cache__device_resident"] = True
    if getattr(args, "trace", None):
        over["obs__trace"] = True
    return base.evolve(**over)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def build_problem(scale: float, pool: int, users: int):
    """Deterministic (graph, params, requests) — identical in every
    worker, so the SPMD dispatch sequence matches without coordination."""
    import jax

    from repro.data.features import make_recsys_feeds
    from repro.graph.executor import init_graph_params
    from repro.models.ranking import (PaperRankingConfig,
                                      build_paper_ranking_model)
    from repro.serve.engine import ServeRequest

    graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(scale))
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    reqs = []
    for u in range(users):
        # ragged pools on purpose: exercises the shard-aligned bucketing
        n = max(1, pool // users + 7 * u)
        feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(u + 1))
        reqs.append(ServeRequest(
            user_id=u,
            user_feeds={k: v for k, v in feeds.items() if k in user_in},
            candidate_feeds={k: v for k, v in feeds.items()
                             if k not in user_in}))
    return graph, params, reqs


def run_worker(args) -> int:
    from repro.dist.topology import Topology

    topo = Topology.from_env().initialize()
    import jax
    import numpy as np

    from repro.common import enable_compile_cache
    from repro.serve.engine import ServingEngine
    from repro.serve.reference import ReferenceScorer, tol_ratio, tolerance

    enable_compile_cache()

    graph, params, reqs = build_problem(args.scale, args.pool, args.users)
    pool_rows = sum(next(iter(r.candidate_feeds.values())).shape[0]
                    for r in reqs)
    # the spawner ships the resolved plan as JSON; a directly-invoked
    # worker (no --plan-json) falls back to building it from its own flags
    plan = (ServePlan.from_json(args.plan_json) if args.plan_json
            else build_plan(args))
    compress = plan.shard.compress_scores
    # fault-tolerance surface (plan.ft): a per-worker FaultInjector whose
    # ``spmd_heartbeat`` site simulates missed per-step heartbeats, fed to
    # a HeartbeatMonitor on a step-counter clock (timeout ~1.5 steps: one
    # missed beat degrades, two consecutive misses declare the worker
    # dead) — the detection layer the elastic-remesh planner consumes.
    injector = monitor = None
    wid = f"w{topo.process_id}"
    hb_step = [0]
    hb_missed = 0
    if plan.ft.inject and plan.ft.sites:
        from repro.ft import FaultInjector, HeartbeatMonitor
        injector = FaultInjector(plan.ft.sites,
                                 seed=plan.ft.seed + topo.process_id)
        monitor = HeartbeatMonitor([wid], timeout=1.5,
                                   clock=lambda: float(hb_step[0]))
    records = []
    tracers = {}
    # process-local float32 reference, shared by every mode (identical
    # inputs in every worker -> identical references)
    ref_scores = ([ReferenceScorer(graph, params)(r) for r in reqs]
                  if args.verify else None)
    for mode in args.modes.split(","):
        mplan = plan.evolve(graph__mode=mode)
        eng = ServingEngine(graph, params, plan=mplan)
        res = eng.score_coalesced(reqs)         # compile + verify pass
        rec = {"mode": mode, "processes": topo.num_processes,
               "shards": int(eng.mesh.devices.size),
               "devices_per_process": len(jax.local_devices()),
               "pool": pool_rows,
               "users": len(reqs),
               "compress_scores": bool(compress),
               "plan": mplan.to_dict()}
        if args.verify:
            atol, rtol = tolerance()
            if compress:
                # int8 wire: per-element error <= that shard's scale/2, on
                # top of the float tolerance
                atol += max(float(np.abs(s).max()) for s in ref_scores) \
                    / 127.0 / 2.0
            ok = all(tol_ratio(a.scores, b, (atol, rtol)) <= 1.0
                     for a, b in zip(res, ref_scores))
            rec["within_int8_bound" if compress else "within_tol"] = ok
            if not ok:
                print(json.dumps(rec), flush=True)
                print(f"[runner] VERIFY FAILED mode={mode}", file=sys.stderr)
                return 1
        if args.bench:
            eng.score_coalesced(reqs)           # warm every shape
            eng.profiler.reset()                # breakdown = timed loop only
            walls = []
            for _ in range(args.passes):
                t0 = time.perf_counter()
                eng.score_coalesced(reqs)
                walls.append(time.perf_counter() - t0)
            wall = float(np.median(walls))
            rec["qps"] = round(len(reqs) / wall, 2)
            rec["rows_per_s"] = round(rec["pool"] / wall, 1)
            # per-phase mean µs per engine call over the timed passes —
            # the same taxonomy as the serve bench's breakdown rows, so
            # the dispatch path stays attributable per shard count
            rec["breakdown"] = eng.profiler.snapshot()
        if monitor is not None:
            from repro.serve.errors import FaultInjected
            hb_step[0] += 1
            try:
                injector.poke("spmd_heartbeat", worker=wid, mode=mode)
                monitor.heartbeat(wid)
            except FaultInjected:
                hb_missed += 1          # this step's beat never arrived
            rec["heartbeat"] = {"worker": wid, "step": hb_step[0],
                                "missed": hb_missed,
                                "dead": monitor.dead()}
            rec["faults"] = injector.stats()
        records.append(rec)
        if eng.tracer is not None:
            tracers[mode] = eng.tracer    # events outlive the engine
        eng.close()
        if topo.process_id == 0:
            print(json.dumps(rec), flush=True)
    if args.trace:
        from repro.obs import write_trace
        write_trace(args.trace, tracers)
    if topo.process_id == 0:
        print(json.dumps({"ok": True, "records": len(records)}), flush=True)
    return 0


def spawn(args) -> int:
    """Re-exec this module once per worker process on localhost.

    Worker output goes to temp files, not pipes: the workers are coupled
    through collectives, so serially draining pipes could deadlock the
    fleet if one worker filled its pipe buffer (chatty XLA/gloo warnings)
    while another held a collective open.
    """
    import tempfile

    on_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    if args.spawn > 1 and not on_cpu:
        print("[runner] --spawn > 1 starts several processes on this host, "
              "which only the CPU can serve (set JAX_PLATFORMS=cpu for "
              "forced host devices); on an accelerator host one process "
              "drives every local chip: use --spawn 1", file=sys.stderr)
        return 2
    port = args.port or _free_port()
    workers = []
    for pid in range(args.spawn):
        env = dict(os.environ)
        env.update({
            "REPRO_NUM_PROCESSES": str(args.spawn),
            "REPRO_PROCESS_ID": str(pid),
            "REPRO_COORDINATOR": f"localhost:{port}",
        })
        if on_cpu:
            env["REPRO_HOST_DEVICES"] = str(args.devices_per_process)
        src = os.path.join(os.path.dirname(__file__), "..", "..")
        env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "repro.dist.runner",
               "--modes", args.modes, "--scale", str(args.scale),
               "--pool", str(args.pool), "--users", str(args.users),
               "--passes", str(args.passes),
               # ONE resolved plan, serialized — workers do not re-derive
               # engine knobs from argv
               "--plan-json", build_plan(args).to_json(indent=None)]
        for flag in ("verify", "bench"):
            if getattr(args, flag):
                cmd.append("--" + flag.replace("_", "-"))
        if args.trace:
            # per-worker trace file; the spawner merges them afterwards
            # with pid = shard index so all workers share one timeline
            cmd += ["--trace", f"{args.trace}.w{pid}"]
        out_f = tempfile.TemporaryFile(mode="w+")
        err_f = tempfile.TemporaryFile(mode="w+")
        workers.append((subprocess.Popen(cmd, env=env, stdout=out_f,
                                         stderr=err_f, text=True),
                        out_f, err_f))
    rc = 0
    deadline = time.monotonic() + args.timeout
    for pid, (p, out_f, err_f) in enumerate(workers):
        try:
            p.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            print(f"[runner] worker {pid} timed out", file=sys.stderr)
            rc = 1
        out_f.seek(0)
        err_f.seek(0)
        out, err = out_f.read(), err_f.read()
        out_f.close()
        err_f.close()
        if pid == 0 and out:
            sys.stdout.write(out)
        if p.returncode != 0:
            print(f"[runner] worker {pid} failed rc={p.returncode}:\n"
                  + err[-3000:], file=sys.stderr)
            rc = 1
    if args.trace and rc == 0:
        from repro.obs import merge_trace_files
        paths = [f"{args.trace}.w{pid}" for pid in range(args.spawn)]
        merge_trace_files(paths, args.trace)    # pid i = shard i
        for p in paths:
            os.remove(p)
        print(f"[runner] merged {args.spawn} worker traces -> {args.trace}")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spawn", type=int, default=0,
                    help="spawn N localhost worker processes and exit")
    ap.add_argument("--devices-per-process", type=int, default=2,
                    help="forced host devices per worker (CPU only; an "
                         "accelerator worker uses every local chip)")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--scale", type=float, default=0.03)
    ap.add_argument("--pool", type=int, default=90)
    ap.add_argument("--users", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="stage-2 row budget (default: the --plan file's "
                         "value, else 256)")
    ap.add_argument("--min-bucket", type=int, default=None,
                    help="smallest bucket (default: the --plan file's "
                         "value, else 16)")
    ap.add_argument("--passes", type=int, default=5)
    ap.add_argument("--verify", action="store_true",
                    help="check sharded scores against the float32 "
                         "reference within the stated tolerance")
    ap.add_argument("--bench", action="store_true",
                    help="emit qps rows per mode")
    ap.add_argument("--device-resident", action="store_true",
                    help="persistent device rep tables + donated stage-2 "
                         "buffers (single-process meshes; multi-process "
                         "engines fall back to per-pack re-stacking)")
    ap.add_argument("--compress-scores", action="store_true",
                    help="opt-in int8-compressed score all-gather")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="base ServePlan JSON file (spawner: sharding is "
                         "forced on top of it)")
    ap.add_argument("--plan-json", default=None, metavar="JSON",
                    help="worker-side: the serialized plan shipped by the "
                         "spawner")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="spawner: merge per-worker Chrome traces here "
                         "(pid = shard index); worker: write own trace")
    ap.add_argument("--timeout", type=int, default=900)
    args = ap.parse_args()
    if args.spawn:
        return spawn(args)
    return run_worker(args)


if __name__ == "__main__":
    sys.exit(main())
