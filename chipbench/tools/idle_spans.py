"""Print how a kept profiler trace divides the chip's idle time among the
program's ``serve.*`` spans (``chipbench/spans.py``), with the shares of
the traced seconds:

  python chipbench/tools/idle_spans.py .chipbench_runs/small

The trace is a ``.xplane.pb`` file or a directory holding one, as
``record_trace.py --trace-dir`` keeps it.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import spans, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else trace.find_xplane(args.trace))
    if path is None:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    red = spans.reduce(path)
    red["shares"] = spans.shares(red)
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())
