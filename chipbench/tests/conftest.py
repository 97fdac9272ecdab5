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

# the served model's registry smoke build (PaperRankingConfig.scaled(0.03)),
# which the configuration's sizes follow
SMOKE = {
    "paper-ranking": {"d_user_profile": 120, "d_item": 15, "d_cross": 15,
                      "seq_len": 4, "d_seq": 8, "d_attn": 8,
                      "d_expert": [15, 8], "d_tower": [8, 8],
                      "d_user_tower": 8},
}


def smoke_config(name: str) -> dict:
    """The benchmark's configuration at the registry's smoke size, served
    without Pallas kernels (the CPU would interpret them)."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(SMOKE[name], build="smoke", preset="paper",
               max_cached_users=256)
    return cfg


# a test-only cell, in no BENCHMARK.json: Zipf users with a warmed rep
# cache, so that the window serves cache hits for the fault that corrupts
# them
TEST_ONLY_CELL = "paper-ranking.zipf-test"
TEST_ONLY_MIX = {"loop": "open", "rate_per_s": 40.0,
                 "users": {"kind": "zipf", "s": 1.1, "universe": 1_000_000},
                 "warm_users": 64, "pool": {"min": 16, "max": 64},
                 "user_feature_pool": 64, "candidate_rows": 1024,
                 "base_seed": 1}


@pytest.fixture
def smoke_root(tmp_path, monkeypatch):
    """A checkout-shaped directory holding BENCHMARK.json (with the
    test-only cell added) and the benchmark's files, with every
    configuration at smoke size and every traffic mix at a CPU's scale."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    dst = tmp_path / bench["paths"][0]
    bench["workloads"].append({"name": TEST_ONLY_CELL,
                               "config": "paper-ranking",
                               "traffic": "zipf-test", "chips": 1})
    for sub in ("reference", "metrics"):
        shutil.copytree(BENCH / sub, dst / sub)
    shutil.copy(BENCH / "peaks.json", dst / "peaks.json")
    (dst / "configs").mkdir()
    for c in bench["configs"]:
        (tmp_path / c["file"]).write_text(json.dumps(smoke_config(c["name"])))
    (dst / "traffic").mkdir()
    for w in bench["workloads"]:
        path = BENCH / "traffic" / f"{w['traffic']}.json"
        mix = (json.loads(path.read_text()) if path.exists()
               else dict(TEST_ONLY_MIX))
        mix.update(pool={"min": 16, "max": 64}, candidate_rows=1024,
                   user_feature_pool=64)
        if mix["loop"] == "closed":
            mix.update(clients=2, requests=4096)
        (dst / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # keep JAX's persistent compile cache off: the harness only turns it
    # on where this variable leaves it unset
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    return tmp_path
