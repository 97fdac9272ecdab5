"""A configuration joins the benchmark by its files alone: a copy of
paper-ranking under another name, added as a configuration file, a
reference and a smoke size, with a cell on an existing traffic mix, gets
the CPU checks without an edit to any test or to the harness."""
import json
import shutil

import pytest

from chipbench import run, spec
from chipbench.tests.conftest import BENCH, ROOT, run_args, smoke_checkout
from chipbench.tests.test_correctness import FAULTS

TWIN = "paper-ranking-twin"
TWIN_CELL = f"{TWIN}.cold-sat"


def _twin_source(tmp_path, smoke_file=True):
    """A checkout holding this repo's benchmark plus the twin, added as
    files (its configuration serves the registry's paper-ranking model),
    and the BENCHMARK.json that lists it."""
    src = tmp_path / "src"
    bdir = src / BENCH.name
    shutil.copytree(BENCH, bdir, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    cfg = json.loads((bdir / "configs" / "paper-ranking.json").read_text())
    cfg["name"] = TWIN
    (bdir / "configs" / f"{TWIN}.json").write_text(json.dumps(cfg))
    shutil.copy(bdir / "reference" / "paper-ranking.py",
                bdir / "reference" / f"{TWIN}.py")
    if smoke_file:
        shutil.copy(bdir / "tests" / "smoke" / "paper-ranking.json",
                    bdir / "tests" / "smoke" / f"{TWIN}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0], "name": TWIN,
                             "file": f"{BENCH.name}/configs/{TWIN}.json"})
    bench["workloads"].append({"name": TWIN_CELL, "config": TWIN,
                               "traffic": "cold-sat-paper-ranking",
                               "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "paper-ranking.cold-sat" in m.get("workloads", []):
            m["workloads"].append(TWIN_CELL)
    return src, bench


def test_a_configuration_added_by_files_is_checked(tmp_path, monkeypatch):
    """The twin's cell runs correct at smoke size, and comes out not
    correct with its stage-2 pack rows shifted."""
    src, bench = _twin_source(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    root = smoke_checkout(tmp_path, bench, src)
    assert spec.cell(spec.load_benchmark(root), TWIN_CELL)

    res = run.run_cell(run_args(TWIN_CELL), root=root, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["reading"]["requests"] > 1
    assert {"setup_s", "candidates_per_s"} <= set(res["metrics"])

    FAULTS["pack_rows_shifted"](monkeypatch)
    res = run.run_cell(run_args(TWIN_CELL), root=root, require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["max_abs_err"]["value"] > \
        res["checks"]["max_abs_err"]["limit"]


def test_a_configuration_without_its_smoke_file_is_named(tmp_path):
    src, bench = _twin_source(tmp_path, smoke_file=False)
    with pytest.raises(FileNotFoundError,
                       match=f"smoke/{TWIN}.json"):
        smoke_checkout(tmp_path, bench, src)
