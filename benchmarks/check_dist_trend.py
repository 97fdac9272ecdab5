"""CI trend gate for the distributed-serving benchmark rows.

Compares a freshly-measured ``--bench dist`` JSON payload against the
committed ``BENCH_dist.json`` baseline and fails (exit 1) when the sharded
stage-2 path regresses. Mirrors ``check_serve_trend``; this gate — not
per-run asserts inside ``bench_dist`` — owns the dist contracts:

* **trend**: every ``dist/*`` qps row present in both files must not
  regress by more than ``--max-regress`` (default 60%) in ``us_per_call``.
  The budget is deliberately generous: each row is a subprocess with its
  own forced host-device world, so CI runners add fork/compile jitter the
  single-process serve rows never see;
* **coverage**: every baseline row must still be emitted by the fresh run
  (a silently dropped shard count would freeze its trend forever);
* **correctness**: every fresh qps row must carry ``within_tol=True`` in
  its derived string — the worker verifies sharded scores against the
  float32 reference within the stated tolerance, and a row that stops
  verifying is a correctness failure, not a perf one;
* **observability**: every fresh qps row must have a sibling
  ``.../breakdown`` row (per-phase pack/dispatch/device/unpack means from
  the worker's ``StageProfiler``), so a qps regression is attributable to
  a phase without rerunning.

Usage (what CI runs):

    python -m benchmarks.run --bench dist --json BENCH_dist_fresh.json
    python -m benchmarks.check_dist_trend \
        --baseline BENCH_dist.json --fresh BENCH_dist_fresh.json

Faster-than-baseline rows are reported but never gate: improvements are
committed by regenerating ``BENCH_dist.json``, which resets the baseline.
"""
from __future__ import annotations

import argparse
import json
import sys


def _rows(payload: dict, *, breakdown: bool) -> dict[str, dict]:
    return {r["name"]: r for r in payload.get("rows", [])
            if r["name"].startswith("dist/")
            and r["name"].endswith("/breakdown") == breakdown}


def check(baseline: dict, fresh: dict, max_regress: float) -> list[str]:
    """Return the list of failure messages (empty == gate passes)."""
    failures: list[str] = []
    base_rows = _rows(baseline, breakdown=False)
    fresh_rows = _rows(fresh, breakdown=False)

    # -- coverage: every baseline qps row must still exist ------------------
    for name in sorted(set(base_rows) - set(fresh_rows)):
        failures.append(f"missing row: {name} (in baseline, not in fresh)")

    # -- trend: per-row regression gate -------------------------------------
    print(f"{'row':44s} {'base_us':>10s} {'fresh_us':>10s} {'delta':>8s}")
    for name in sorted(set(base_rows) & set(fresh_rows)):
        b = float(base_rows[name]["us_per_call"])
        f = float(fresh_rows[name]["us_per_call"])
        delta = (f - b) / b if b else 0.0
        mark = ""
        if delta > max_regress:
            mark = "  << REGRESSION"
            failures.append(
                f"regression: {name} {b:.1f}us -> {f:.1f}us "
                f"({delta:+.0%} > {max_regress:.0%} budget)")
        print(f"{name:44s} {b:10.1f} {f:10.1f} {delta:+7.0%}{mark}")

    # -- correctness + breakdown sibling on the FRESH run --------------------
    fresh_bd = _rows(fresh, breakdown=True)
    for name in sorted(fresh_rows):
        if "within_tol=True" not in fresh_rows[name].get("derived", ""):
            failures.append(
                f"correctness: {name} no longer verifies against the "
                f"float32 reference "
                f"(derived={fresh_rows[name].get('derived')!r})")
        if f"{name}/breakdown" not in fresh_bd:
            failures.append(f"missing breakdown row: {name}/breakdown")

    for name in sorted(fresh_bd):
        print(f"# {name}: {fresh_bd[name].get('derived', '')}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_dist.json",
                    help="committed dist bench JSON (the trend baseline)")
    ap.add_argument("--fresh", default="BENCH_dist_fresh.json",
                    help="dist bench JSON from this run")
    ap.add_argument("--max-regress", type=float, default=0.60,
                    help="per-row us_per_call regression budget "
                         "(0.60 = fail beyond +60%%; generous because each "
                         "row forks its own device world)")
    args = ap.parse_args()
    with open(args.baseline) as fh:
        baseline = json.load(fh)
    with open(args.fresh) as fh:
        fresh = json.load(fh)
    failures = check(baseline, fresh, args.max_regress)
    if failures:
        print(f"\nFAIL: {len(failures)} dist trend violation(s)")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print("\nOK: dist rows within trend budget, tolerance + breakdown hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
