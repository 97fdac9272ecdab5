"""A configuration, a traffic mix or a per-layer metric is added by adding
a file: the harness finds each by the name BENCHMARK.json gives it."""
import json

import pytest

from chipbench import run, spec


def test_files_dropped_into_a_checkout_are_found_by_name(tmp_path):
    bench = {
        "paths": ["bench"],
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1},
                      {"name": "toy.other", "config": "toy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "p99_ms", "workloads": ["toy.burst"]}],
        "per_layer": [{"name": "new_metric.x", "moves": "p99_ms"},
                      {"name": "listed", "moves": "setup_s",
                       "workloads": ["toy.other"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "reference"):
        (d / sub).mkdir(parents=True)
    (d / "configs" / "toy.json").write_text('{"width": 7}')
    (d / "traffic" / "burst.json").write_text('{"loop": "open"}')
    (d / "metrics" / "new_metric.x.py").write_text(
        "def read(w):\n    return w['x'] * 2\n")
    (d / "reference" / "toy.py").write_text(
        "def param_shapes(cfg):\n    return {'l': {'w': (cfg['width'], 1)}}\n")

    b = spec.load_benchmark(tmp_path)
    assert spec.cell(b, "toy.burst")["traffic"] == "burst"
    assert spec.load_config(tmp_path, b, "toy") == {"width": 7}
    assert spec.load_traffic(tmp_path, b, "burst") == {"loop": "open"}
    assert spec.load_reader(tmp_path, b, "new_metric.x")({"x": 4}) == 8
    ref = spec.load_reference(tmp_path, b, "toy")
    assert ref.param_shapes({"width": 7}) == {"l": {"w": (7, 1)}}
    # a metric without a workloads list is reported wherever the metric it
    # moves is; a listed one only in its cells
    assert [m["name"] for m in spec.cell_metrics(b, "toy.burst",
                                                 "per_layer")] == [
        "new_metric.x"]
    assert [m["name"] for m in spec.cell_metrics(b, "toy.other",
                                                 "per_layer")] == ["listed"]
    assert [m["name"] for m in spec.cell_metrics(b, "toy.other",
                                                 "end_to_end")] == [
        "setup_s"]
    with pytest.raises(KeyError):
        spec.cell(b, "toy.missing")


def test_every_cell_of_the_benchmark_has_its_files():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        spec.load_config(spec.ROOT, b, w["config"])
        spec.load_traffic(spec.ROOT, b, w["traffic"])
        spec.load_reference(spec.ROOT, b, w["config"])
        for kind in ("end_to_end", "per_layer"):
            for m in spec.cell_metrics(b, w["name"], kind):
                if kind == "per_layer":
                    assert callable(spec.load_reader(spec.ROOT, b,
                                                     m["name"]))
        names = {m["name"] for m in spec.cell_metrics(b, w["name"],
                                                      "end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        assert spec.cell_metrics(b, w["name"], "per_layer")
    # each configuration's reference and its smoke size for the CPU checks
    for c in b["configs"]:
        for rel in (f"reference/{c['name']}.py",
                    f"tests/smoke/{c['name']}.json"):
            path = spec.bench_dir(spec.ROOT, b) / rel
            assert path.is_file(), f"configuration {c['name']!r} lacks {path}"


def test_the_run_refuses_a_device_that_is_not_a_tpu(capsys):
    b = spec.load_benchmark()
    cell = b["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err
