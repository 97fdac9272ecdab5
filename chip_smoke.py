"""Chip smoke test: serve the paper's ranking model and DIN at their
published widths on a TPU, through the normal serving path, and check
every score against the plain float32 reference.

  python chip_smoke.py            # one chip: the two scenarios, two presets
  python chip_smoke.py --chips 4  # the 4-chip candidate-sharded engine only

One chip: ``python -m repro.launch.serve --scenario paper-ranking,din
--no-smoke`` (``RankingService`` -> batcher -> engine -> rep store) runs
under the ``paper`` preset with the device-resident rep tier, then under the
``tpu`` preset with the compiled Pallas kernels. Each run submits a few
Zipf-distributed users, each with a 2048-candidate pool, all at once, twice
(compile pass, timed pass). Every score of both passes must be within the
stated TPU tolerance (``repro.serve.reference.SCORE_TOL``) of the
un-rewritten graph run by ``Executor(graph, "vani")`` at highest matmul
precision on the same feeds. Under ``tpu`` every stage-2 executable must
contain the kernels (``tpu_custom_call``).

``--chips 4``: the paper-ranking engine with candidate-axis sharding on a
4-chip 'cand' mesh in this one process, against the same engine on one
chip.

The last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when every phase passed on a TPU. Anything else exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
sys.path.insert(0, _SRC)

SCENARIOS = ("paper-ranking", "din")
# (preset, extra launcher flags): the paper preset on the device-resident
# rep tier, the tpu preset as it stands (compiled Pallas kernels)
PRESET_RUNS = (("paper", ["--device-resident"]), ("tpu", []))


def require(cond, msg) -> None:
    """A failed phase check: raises (unlike ``assert``, also under -O)."""
    if not cond:
        raise RuntimeError(msg)


def _check_scores(name, results, refs, tol) -> tuple[float, float]:
    """Every result finite, reference-shaped and within ``tol``. Returns
    (max |err|, max err / tolerance)."""
    import numpy as np

    from repro.serve.reference import tol_ratio

    err = ratio = 0.0
    for res, ref in zip(results, refs):
        s = np.asarray(res.scores)
        require(s.shape == ref.shape,
                f"{name}: shape {s.shape} != {ref.shape}")
        require(np.isfinite(s).all(), f"{name}: non-finite scores")
        err = max(err, float(np.abs(s - ref).max()))
        ratio = max(ratio, tol_ratio(s, ref, tol))
    require(ratio <= 1.0, f"{name}: max error {err:.3g} is {ratio:.2f}x the "
                          f"stated tolerance (atol, rtol)={tol}")
    return err, ratio


def serve_phase(preset: str, flags: list[str], *, smoke: bool = False,
                requests: int = 8, candidates: int = 2048) -> dict:
    """One launcher run of both scenarios under ``preset``, checked
    against the float32 reference. Returns a per-scenario report."""
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.serve.reference import ReferenceScorer, tolerance

    tol = tolerance()
    report = {}

    def inspect(svc, items, passes, pass_s):
        cache = svc.stats()["shared_cache"]
        require(cache["hits"] > 0, f"{preset}: no rep-cache hit: {cache}")
        for sc in svc.scenarios:
            eng = svc.engine(sc)
            idx = [i for i, (s, _) in enumerate(items) if s == sc]
            t0 = time.perf_counter()
            ref = ReferenceScorer(svc.source_graph(sc), svc.source_params(sc))
            refs = [ref(items[i][1]) for i in idx]
            ref_s = time.perf_counter() - t0
            err, ratio = _check_scores(
                f"{preset}/{sc}",
                [p[i] for p in passes for i in idx], refs * len(passes), tol)
            require(eng.coalesced_calls > 0, f"{preset}/{sc}: no coalescing")
            if "--device-resident" in flags:
                require(eng.device_resident and eng.device_store.writes > 0,
                        f"{preset}/{sc}: the device rep tier was not used")
            exes = eng.stage2_executables()
            kernels = all("tpu_custom_call" in c.as_text()
                          for c in exes.values())
            # the CPU interprets the kernels: only a TPU compiles them in
            if eng.plan.kernel.use_pallas and jax.default_backend() == "tpu":
                require(kernels, f"{preset}/{sc}: a stage-2 executable has "
                                 f"no Pallas kernel (tpu_custom_call)")
            lat = np.asarray([passes[1][i].latency_ms for i in idx])
            report[sc] = {
                "compile_pass_s": pass_s[0], "timed_pass_s": pass_s[1],
                "reference_s": ref_s,
                "latency_ms_p50": float(np.percentile(lat, 50)),
                "latency_ms_max": float(lat.max()),
                "stage2_compilations": eng.stage2_compilations,
                "stage2_signatures": sorted(exes),
                "tpu_custom_call": kernels,
                "coalesced_calls": eng.coalesced_calls,
                "cache_hits": cache["hits"],
                "max_abs_err": err, "max_err_over_tol": ratio,
                "requests": len(idx), "candidates": candidates}

    argv = ["--scenario", ",".join(SCENARIOS), "--preset", preset,
            "--requests", str(requests), "--candidates", str(candidates),
            "--smoke" if smoke else "--no-smoke", *flags]
    serve.main(argv, inspect=inspect)
    require(set(report) == set(SCENARIOS), f"scenarios served: {report}")
    return report


def shard_phase(*, smoke: bool = False, requests: int = 4,
                candidates: int = 2048) -> dict:
    """paper-ranking on a 4-device 'cand' mesh vs the same engine on one
    device, both checked against each other within the stated tolerance."""
    import jax
    import numpy as np

    from repro import configs
    from repro.graph.executor import init_graph_params
    from repro.launch.serve import request_stream, zipf_users
    from repro.serve import ServePlan, ServingEngine
    from repro.serve.reference import ReferenceScorer, tolerance

    tol = tolerance()
    mod = configs.get_config("paper-ranking")
    graph, _ = (mod.smoke_build() if smoke else mod.BUILD)()
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}

    def split(feeds):
        return ({k: v for k, v in feeds.items() if k in user_in},
                {k: v for k, v in feeds.items() if k not in user_in})

    reqs = request_stream(graph, split, zipf_users(requests), candidates)
    plan = ServePlan.preset("distributed")
    one = ServingEngine(graph, params,
                        plan=plan.evolve(shard__shard_candidates=False))
    four = ServingEngine(graph, params, plan=plan)
    try:
        require(four.mesh.devices.size == 4, f"mesh: {four.mesh}")
        t0 = time.perf_counter()
        r1 = one.score_coalesced(reqs)
        one_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r4 = four.score_coalesced(reqs)
        four_s = time.perf_counter() - t0
        err, ratio = _check_scores("4 chips vs 1", r4,
                                   [np.asarray(r.scores) for r in r1], tol)
        ref = ReferenceScorer(graph, params)
        ref_err, ref_ratio = _check_scores("4 chips vs reference", r4,
                                           [ref(r) for r in reqs], tol)
        spread = set()
        for c in four.stage2_executables().values():
            for s in jax.tree.leaves(c.output_shardings):
                require(len(s.device_set) == 4 and not s.is_fully_replicated,
                        f"stage-2 output not sharded over the 4 chips: {s}")
                spread.add(str(s.spec))
        return {"mesh_devices": int(four.mesh.devices.size),
                "output_specs": sorted(spread),
                "stage2_compilations": four.stage2_compilations,
                "one_chip_first_call_s": one_s,
                "four_chip_first_call_s": four_s,
                "max_abs_err_vs_one_chip": err,
                "max_err_over_tol_vs_one_chip": ratio,
                "max_abs_err_vs_reference": ref_err,
                "max_err_over_tol_vs_reference": ref_ratio,
                "requests": len(reqs), "candidates": candidates}
    finally:
        one.close()
        four.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the candidate-sharded 4-chip path")
    args = ap.parse_args(argv)

    import jax

    from repro.common import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    print(f"[smoke] device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but {len(devs)} "
              f"device(s)", file=sys.stderr)
        return 1
    from repro.serve.reference import tolerance
    print(f"[smoke] compile_cache={enable_compile_cache()} "
          f"tolerance (atol, rtol)={tolerance()}", flush=True)

    t_all = time.perf_counter()
    if args.chips == 4:
        rep = shard_phase()
        print(f"[smoke] shard {json.dumps(rep)}", flush=True)
    else:
        for preset, flags in PRESET_RUNS:
            for sc, rep in serve_phase(preset, flags).items():
                print(f"[smoke] preset={preset} scenario={sc} "
                      f"{json.dumps(rep)}", flush=True)
    print(f"[smoke] total {time.perf_counter() - t_all:.1f}s", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
