import json
import os
import pathlib
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

from chipbench import run, spec  # noqa: E402

# what the CPU checks are parametrized over: every configuration and cell
# that BENCHMARK.json lists
_BENCHMARK = spec.load_benchmark()
CONFIGS = [c["name"] for c in _BENCHMARK["configs"]]
CELLS = [w["name"] for w in _BENCHMARK["workloads"]]

# a test-only traffic mix, in no BENCHMARK.json: Zipf users with a warmed
# rep cache, so that the window serves cache hits for the fault that
# corrupts them; each configuration gets a cell `<config>.zipf-test` on it
TEST_ONLY_TRAFFIC = "zipf-test"
TEST_ONLY_MIX = {"loop": "open", "rate_per_s": 40.0,
                 "users": {"kind": "zipf", "s": 1.1, "universe": 1_000_000},
                 "warm_users": 64, "pool": {"min": 16, "max": 64},
                 "user_feature_pool": 64, "candidate_rows": 1024,
                 "base_seed": 1}


def zipf_test_cell(config: str) -> str:
    return f"{config}.{TEST_ONLY_TRAFFIC}"


def run_args(cell: str, seed: int = 2**31 + 77):
    return run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", "1", "--trace", "0"])


def smoke_config(name: str, root: pathlib.Path = ROOT,
                 bench: dict | None = None) -> dict:
    """Configuration ``name`` of ``bench`` (default: ``root``'s
    BENCHMARK.json) at its smoke size, served without Pallas kernels (the
    CPU would interpret them). The smoke size is
    ``tests/smoke/<name>.json`` in the benchmark's directory: overrides
    that follow the served model's registry smoke build."""
    bench = spec.load_benchmark(root) if bench is None else bench
    path = spec.bench_dir(root, bench) / "tests" / "smoke" / f"{name}.json"
    cfg = spec.load_config(root, bench, name)
    cfg.update(json.loads(path.read_text()), build="smoke", preset="paper",
               max_cached_users=256)
    return cfg


def smoke_checkout(dst: pathlib.Path, bench: dict,
                   src: pathlib.Path = ROOT) -> pathlib.Path:
    """Write into ``dst`` a checkout-shaped directory: ``bench`` as its
    BENCHMARK.json, with a test-only cell on the test-only mix for each
    configuration, and the benchmark's files from the checkout ``src``,
    with every configuration at smoke size and every traffic mix at a
    CPU's scale."""
    bench = json.loads(json.dumps(bench))
    bench["workloads"] += [{"name": zipf_test_cell(c["name"]),
                            "config": c["name"], "traffic": TEST_ONLY_TRAFFIC,
                            "chips": 1} for c in bench["configs"]]
    src_dir, dst_dir = spec.bench_dir(src, bench), spec.bench_dir(dst, bench)
    for sub in ("reference", "metrics"):
        shutil.copytree(src_dir / sub, dst_dir / sub)
    shutil.copy(src_dir / "peaks.json", dst_dir / "peaks.json")
    for c in bench["configs"]:
        path = dst / c["file"]
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(smoke_config(c["name"], src, bench)))
    (dst_dir / "traffic").mkdir()
    for name in {w["traffic"] for w in bench["workloads"]}:
        mix = (dict(TEST_ONLY_MIX) if name == TEST_ONLY_TRAFFIC
               else spec.load_traffic(src, bench, name))
        mix.update(pool={"min": 16, "max": 64}, candidate_rows=1024,
                   user_feature_pool=64)
        if mix["loop"] == "closed":
            mix.update(clients=2, requests=4096)
        (dst_dir / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


@pytest.fixture
def smoke_root(tmp_path, monkeypatch):
    """The smoke checkout of this repo's BENCHMARK.json, with JAX's
    persistent compile cache kept off (the harness only turns it on where
    JAX_COMPILATION_CACHE_DIR is unset)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    return smoke_checkout(tmp_path, spec.load_benchmark())
