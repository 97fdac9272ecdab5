"""Find a cell's pieces by name, from ``BENCHMARK.json`` and the files of
the benchmark's directory, so that a configuration, a traffic mix or a
per-layer metric is added by adding a file:

- ``configs/<config>.json``: the configuration as run (the entry's
  ``file``), with its sizes, serving plan and correctness limit;
- ``reference/<config>.py``: its plain reference and its arithmetic;
- ``traffic/<traffic>.json``: the parameters the general generator
  (``traffic.py``) reads;
- ``metrics/<metric>.py``: a reader ``read(window) -> float | None`` of one
  per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def bench_dir(root: pathlib.Path, bench: dict) -> pathlib.Path:
    return root / bench["paths"][0]


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(root: pathlib.Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r}")


def load_traffic(root: pathlib.Path, bench: dict, name: str) -> dict:
    return json.loads(
        (bench_dir(root, bench) / "traffic" / f"{name}.json").read_text())


def _module(path: pathlib.Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(root: pathlib.Path, bench: dict, config: str):
    return _module(bench_dir(root, bench) / "reference" / f"{config}.py",
                   "reference")


def load_reader(root: pathlib.Path, bench: dict, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _module(bench_dir(root, bench) / "metrics" / f"{metric}.py",
                   "metric").read


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, or that list no cells and move (or are) a metric the
    cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
