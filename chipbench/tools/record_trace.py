"""Record a short profiler trace of one cell for the trace-reduction test
(``chipbench/testdata``): a run of the cell whose traced stretch is
``--trace-seconds`` long, kept under ``--trace-dir``.

  python chipbench/tools/record_trace.py --workload paper-ranking.cold-sat \
      --seed 5 --seconds 2 --trace 1 --trace-dir .chipbench_runs/small \
      --trace-seconds 0.05
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--trace-seconds")
    run.TRACE_SECONDS = float(argv[i + 1])
    del argv[i:i + 2]
    res = run.run_cell(run.parse_args(argv))
    if res is None:
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
