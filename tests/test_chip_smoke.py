"""``chip_smoke.py`` (the repo-root chip smoke test) off the chip: it must
refuse to report success without a TPU or outside a checkout, and its
phases must pass on the CPU at smoke sizes — the same launcher path, the
same reference checks, the CPU tolerance, the kernels interpreted."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run(args, cwd, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_refuses_without_a_tpu():
    p = _run(["chip_smoke.py"], ROOT)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run(["chip_smoke.py"], tmp_path)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


@pytest.mark.parametrize("preset,flags", chip_smoke.PRESET_RUNS,
                         ids=[p for p, _ in chip_smoke.PRESET_RUNS])
def test_serve_phase(preset, flags):
    rep = chip_smoke.serve_phase(preset, flags, smoke=True, requests=6,
                                 candidates=64)
    assert set(rep) == set(chip_smoke.SCENARIOS)
    for r in rep.values():
        assert r["max_err_over_tol"] <= 1.0
        assert r["stage2_compilations"] >= 1 and r["coalesced_calls"] >= 1
        assert r["cache_hits"] > 0 and r["requests"] == 3


def test_shard_phase_on_four_host_devices():
    code = ("import json, chip_smoke; print(json.dumps("
            "chip_smoke.shard_phase(smoke=True, candidates=64)))")
    p = _run(["-c", code], ROOT,
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert p.returncode == 0, p.stderr[-3000:]
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    assert rep["mesh_devices"] == 4
    assert rep["output_specs"] == ["PartitionSpec('cand',)"]
    assert rep["max_err_over_tol_vs_one_chip"] <= 1.0
    assert rep["max_err_over_tol_vs_reference"] <= 1.0
