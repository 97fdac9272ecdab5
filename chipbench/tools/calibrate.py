"""Readings that set a cell's correctness limit: for each seed, one short
run of the cell (set-up, window at the cell's own load, the comparison
with the reference) in this one process, with the reference at each
control precision also put in the program's place on the same sample and
judged by the same comparison. Prints one JSON line a seed.

  python chipbench/tools/calibrate.py --workload paper-ranking.cold-sat \
      --seconds 3 --seeds 11,12,13

The control is the reference with every matmul operand rounded to
float8 (e4m3), the step below the bf16 operands that the configuration's
DEFAULT-precision matmuls use on a TPU; ``bf16`` rounds them to bfloat16,
which is what the served path already computes with.

Set-up here skips the rep-cache fill (``warm_users`` 0): a served score does
not depend on whether its user's reps came from the cache, and a Zipf
window still serves both hits and misses.
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
import jax.numpy as jnp  # noqa: E402

from chipbench import model, run  # noqa: E402

CONTROLS = {"fp8_e4m3": model.mm_rounded(jnp.float8_e4m3fn),
            "bf16": model.mm_rounded(jnp.bfloat16)}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--seeds")
    seeds = [int(s) for s in argv[i + 1].split(",")]
    del argv[i:i + 2]
    for seed in seeds:
        args = run.parse_args(argv + ["--seed", str(seed)])
        res = run.run_cell(args, controls=CONTROLS,
                           mix_over={"warm_users": 0})
        if res is None:
            return 2
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "reading": res["reading"],
                          "controls": res["controls"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
