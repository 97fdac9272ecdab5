"""Find a cell's operating point on the chip: after one set-up, one window
per open-loop rate (``--rates``) or per closed-loop client count
(``--clients``), each printed as a JSON line. ``--reps`` repeats the
points, every other pass in reverse order, so that a drift of the machine
over the sweep falls on every point alike; each window has a seed of its
own.

  python chipbench/tools/sweep.py --workload paper-ranking.cold-sat --seed 5 \
      --seconds 30 --clients 1,2,4,8,16,32 --reps 3
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    points = []
    for flag, key, cast in (("--rates", "rate_per_s", float),
                            ("--clients", "clients", int)):
        if flag in argv:
            i = argv.index(flag)
            points = [{key: cast(v)} for v in argv[i + 1].split(",")]
            del argv[i:i + 2]
    reps = 1
    if "--reps" in argv:
        i = argv.index("--reps")
        reps = int(argv[i + 1])
        del argv[i:i + 2]
    order = [dict(p, rep=r)
             for r in range(reps)
             for p in (points if r % 2 == 0 else points[::-1])]
    out = run.run_cell(run.parse_args(argv), sweep=order)
    if out is None:
        return 2
    for p in out["sweep"]:
        print(json.dumps(p), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
