"""What decides ``correct``, driven through the rest of a run on the CPU at
smoke size (the harness's look for a chip skipped): the float8 control
fails the configuration's limit where the program passes it, and a run
whose timed path is broken underneath comes out as not correct."""
import json

import jax.numpy as jnp
import pytest

from chipbench import model, run
from chipbench.tests.conftest import CELLS, CONFIGS, run_args, zipf_test_cell
from chipbench.tools.calibrate import CONTROLS


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limit_that_the_program_passes(smoke_root,
                                                              cell):
    """The float8 reference put in the program's place is judged by the same
    comparison and verdict as the served answers, and comes out not
    correct where the program comes out correct."""
    res = run.run_cell(run_args(cell), root=smoke_root, require_tpu=False,
                       controls={"fp8_e4m3": CONTROLS["fp8_e4m3"]})
    limit = res["checks"]["max_abs_err"]["limit"]
    assert res["correct"], res["checks"]
    assert res["reading"]["max_abs_err"] <= limit
    assert res["reading"]["requests"] > 1
    control = res["controls"]["fp8_e4m3"]
    assert control["correct"] is False
    assert control["max_abs_err"] > limit
    # the result line's shape
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert "setup_s" in res["metrics"]


def _alter_answers(monkeypatch):
    """Every served answer has its first score moved by 0.5 where the
    engine hands it out."""
    from repro.serve import engine as engine_mod
    collect = engine_mod.ServingEngine.collect

    def altered(self, handle):
        out = collect(self, handle)
        for r in out:
            r.scores = r.scores.copy()
            r.scores[0, 0] += 0.5
        return out

    monkeypatch.setattr(engine_mod.ServingEngine, "collect", altered)


def _shift_pack_rows(monkeypatch):
    """Every stage-2 pack scores its candidate rows shifted by one, so each
    row's score lands on its neighbour."""
    import jax.numpy as jnp
    from repro.serve import engine as engine_mod
    prepare = engine_mod.ServingEngine._prepare_pack

    def shifted(self, *a, **kw):
        table, uidx, cand, n_slots, first = prepare(self, *a, **kw)
        cand = {k: jnp.roll(v, 1, axis=0) for k, v in cand.items()}
        return table, uidx, cand, n_slots, first

    monkeypatch.setattr(engine_mod.ServingEngine, "_prepare_pack", shifted)


def _hit_serves_another_user(monkeypatch):
    """A rep-cache hit hands out the reps of another cached user."""
    from repro.serve import cache as cache_mod
    get = cache_mod.UserRepCache.get

    def other(self, key):
        reps = get(self, key)
        if reps is None:
            return None
        with self._lock:
            for uid, (_, r) in self._entries.items():
                if uid != key[0]:
                    return r
        return reps

    monkeypatch.setattr(cache_mod.UserRepCache, "get", other)


FAULTS = {"answer_altered": _alter_answers,
          "pack_rows_shifted": _shift_pack_rows,
          "hit_serves_another_user": _hit_serves_another_user}


@pytest.mark.parametrize(("fault", "cell"), [
    *((f, c) for f in ("answer_altered", "pack_rows_shifted") for c in CELLS),
    # the committed cells serve no cache hit; each configuration's
    # test-only Zipf cell does
    *(("hit_serves_another_user", zipf_test_cell(c)) for c in CONFIGS)])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        smoke_root, monkeypatch, fault, cell):
    """A run whose timed path is broken underneath comes out not correct,
    for each fault a serving cell can have: an answer altered where the
    engine produces it, candidate rows misplaced in a stage-2 pack, and a
    rep-cache hit that serves another user's reps."""
    FAULTS[fault](monkeypatch)
    res = run.run_cell(run_args(cell), root=smoke_root, require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["max_abs_err"]["value"] > \
        res["checks"]["max_abs_err"]["limit"]
    assert json.loads(json.dumps(res))["correct"] is False


def test_the_test_only_cell_serves_cache_hits(smoke_root):
    """The cache-hit fault has hits to corrupt: each configuration's
    test-only cell's sample holds cache hits, and the unbroken run is
    correct."""
    for config in CONFIGS:
        res = run.run_cell(run_args(zipf_test_cell(config)),
                           root=smoke_root, require_tpu=False)
        assert res["correct"], (config, res["checks"])
        assert res["reading"]["hits"] > 0, config


def test_controls_round_the_operands():
    a = jnp.asarray([[1.0 + 2.0**-12]])
    b = jnp.asarray([[1.0]])
    assert float(model.mm_highest("ij,jk->ik", a, b)[0, 0]) == 1.0 + 2.0**-12
    assert float(CONTROLS["bf16"]("ij,jk->ik", a, b)[0, 0]) == 1.0
    assert float(CONTROLS["fp8_e4m3"]("ij,jk->ik",
                                      jnp.asarray([[1.1]]), b)[0, 0]) == 1.125
